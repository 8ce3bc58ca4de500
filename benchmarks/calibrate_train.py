#!/usr/bin/env python3
"""Read, on the chip, what a ``train`` cell's limits are set from.

    python3 benchmarks/calibrate_train.py --workload <name> --seeds 1,2,3 [--controls 3]

For each seed, in one process: the cell's own set-up and warm-up epoch (no
window: training's readings need none), the reference, and the gaps between
them (the lower readings).  For the first ``--controls`` seeds also the
control (the reference put in the program's place with every product cast
to float8 as the program casts to bfloat16), the same in bfloat16 (a
second witness for the program's own readings), and the fault that a one-chip training cell can have
besides an unchanged state: half of the batch left out, the mean taken
over the rest (the upper readings).  One JSON line per seed on standard
output.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import compare  # noqa: E402
import config_io  # noqa: E402
from drivers import train  # noqa: E402


def values(numbers):
    return {k: v[0] for k, v in numbers.items()}


def one_seed(cell, cfg, seed, controls, directory=None):
    t0 = time.perf_counter()
    s = train.setup(cell, cfg, seed)
    got, multipliers, numbers = train.program_readings(s, cell, cfg)
    feed, struct, phases = s["feed"], s["struct"], s["phases"]
    s["trainer"].wstate = None
    s.clear()
    ref, _ = train.check(cell, cfg, feed, multipliers, seed, struct)
    numbers.update(compare.compare_training(got, ref))
    out = {"seed": seed, "program": values(numbers),
           "details": {k: v[1] for k, v in numbers.items()},
           "setup_phases": phases, "norms": {"program": got,
                                             "reference": ref}}
    if controls:
        low, _ = train.check(cell, cfg, feed, multipliers, seed, struct,
                             cast="float8")
        out["control_float8"] = values(compare.compare_training(low, ref))
        out["norms"]["control_float8"] = low
        bf, _ = train.check(cell, cfg, feed, multipliers, seed, struct,
                            cast="bfloat16")
        out["reference_bfloat16"] = values(compare.compare_training(bf, ref))
        out["norms"]["reference_bfloat16"] = bf
        half, _ = train.check(cell, cfg, feed, multipliers, seed,
                              struct, rows_kept=0.5)
        out["fault_half_batch"] = values(compare.compare_training(half, ref))
        out["norms"]["fault_half_batch"] = half
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--directory", default=None,
                    help="where the cell's and the configuration's files "
                         "are, if not under benchmarks/")
    args = ap.parse_args(argv)
    cell = config_io.load_cell(args.workload, args.directory)
    cfg = config_io.load_config(cell["config"], args.directory)
    train.configure_program()
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        print(json.dumps(one_seed(cell, cfg, seed, i < args.controls)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
