#!/usr/bin/env python3
"""Read, on the chip, what a ``train_counted`` cell's limits are set from.

    python3 benchmarks/calibrate_counted.py --workload <name> --seeds 1,2,3 [--controls 2]

For each seed, in one process: the cell's own set-up and warm-up epoch (no
window), the reference, and the gaps between them (the lower readings).
For the first ``--controls`` seeds also the control (the reference put in
the program's place with every product cast to float8), the same in
bfloat16 (a second witness for the program's own readings), the planted
fault of this driver's mechanism (the routed experts left out, the shared
expert kept) and, reported and not judged, the reference with the window
ignored in its sliding layers.  One JSON line per seed on standard
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

import config_io  # noqa: E402
from drivers import train_counted as driver  # noqa: E402


def values(numbers):
    return {k: v[0] for k, v in numbers.items()}


CONTROLS = {
    "control_float8": {"cast": "float8"},
    "reference_bfloat16": {"cast": "bfloat16"},
    "fault_routed_left_out": {"leave_out": ("routed_experts",)},
    "window_ignored": {"leave_out": ("window",)},
}


def one_seed(cell, cfg, seed, controls=tuple(CONTROLS)):
    t0 = time.perf_counter()
    s = driver.setup(cell, cfg, seed)
    got = driver.program_readings(s)
    feed, struct, phases = s["feed"], s["struct"], s["phases"]
    s["trainer"].wstate = None
    s["trainer"]._train_step = s["trainer"]._eval_step = None
    s.clear()
    ref, _ = driver.check(cfg, feed, seed, struct)
    numbers = driver.compare_all(got, ref)
    out = {"seed": seed, "program": values(numbers),
           "details": {k: v[1] for k, v in numbers.items()},
           "setup_phases": phases,
           "norms": {"program": got, "reference": ref}}
    for name in controls:
        other, _ = driver.check(cfg, feed, seed, struct,
                                **CONTROLS[name])
        out[name] = values(driver.compare_all(other, ref))
        out["norms"][name] = other
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--directory", default=None,
                    help="where the cell's and the configuration's files "
                         "are, if not under benchmarks/")
    args = ap.parse_args(argv)
    cell = config_io.load_cell(args.workload, args.directory)
    cfg = config_io.load_config(cell["config"], args.directory)
    driver.configure_program()
    for i, seed in enumerate(int(x) for x in args.seeds.split(",")):
        print(json.dumps(one_seed(
            cell, cfg, seed, tuple(CONTROLS) if i < args.controls else ())),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
