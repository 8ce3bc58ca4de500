#!/usr/bin/env python3
"""``calibrate_counted.py`` with the controls of a configuration whose
reference is ``references/nemotron_h.py``: what that cell's limits are
set from, read on the chip.

    python3 benchmarks/calibrate_hybrid.py --workload <name> --seeds 1,2 [--controls 2]

The same seeds, readings and output as ``calibrate_counted.py`` (its
``one_seed`` and ``main`` run unedited); only the table of controls
differs: the float8 control, the same in bfloat16 (a second witness for
the program's own readings), and that reference's three planted faults:
the scan's state reset at every chunk boundary, the convolution skipped,
the routed experts left out.  The benchmark's own runs never run this.
"""

import sys

import calibrate_counted

CONTROLS = {
    "control_float8": {"cast": "float8"},
    "reference_bfloat16": {"cast": "bfloat16"},
    "fault_ssm_carry": {"leave_out": ("ssm_carry",)},
    "fault_conv": {"leave_out": ("conv",)},
    "fault_routed_left_out": {"leave_out": ("routed_experts",)},
}

if __name__ == "__main__":
    calibrate_counted.CONTROLS.clear()
    calibrate_counted.CONTROLS.update(CONTROLS)
    sys.exit(calibrate_counted.main())
