#!/usr/bin/env python3
"""``calibrate_counted.py`` with the controls of a configuration whose
reference is ``references/olmo_hybrid.py``: what that cell's limits are
set from, read on the chip.

    python3 benchmarks/calibrate_linear.py --workload <name> --seeds 1,2 [--controls 2]

The same seeds, readings and output as ``calibrate_counted.py`` (its
``one_seed`` and ``main`` run unedited); only the table of controls
differs: the float8 control, the same in bfloat16 (a second witness for
the program's own readings), and that reference's three planted faults:
the delta rule's state reset at every chunk boundary, its correction
term left out (plain gated linear attention), the convolutions skipped.
The benchmark's own runs never run this.

On the chip the float8 control reads not-a-number from the first
backward pass on, not a gap (PERF.md section 6, PR 36): the loss at step
1 is finite, every gradient from the last block's MLP down is not.  A
norm after every sublayer divides the gradient that comes back by the
sublayer's small RMS, and float8 (e4m3: largest number 448, no infinity)
does not hold the result; with the gradients rounded to e5m2 (57,344)
and the rest as it is, a control tried on the chip and not kept, it read
the same, and which division it was is not isolated.  The tiny cell of
the tests reads a gap.
"""

import sys

import calibrate_counted

CONTROLS = {
    "control_float8": {"cast": "float8"},
    "reference_bfloat16": {"cast": "bfloat16"},
    "fault_delta_carry": {"leave_out": ("delta_carry",)},
    "fault_delta_term": {"leave_out": ("delta_term",)},
    "fault_conv": {"leave_out": ("conv",)},
}

if __name__ == "__main__":
    calibrate_counted.CONTROLS.clear()
    calibrate_counted.CONTROLS.update(CONTROLS)
    sys.exit(calibrate_counted.main())
