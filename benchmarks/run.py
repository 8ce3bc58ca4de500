#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's file, its configuration's file, its driver and its
metrics' readers by the names in ``BENCHMARK.json``; refuses to measure
without the chips the cell asks for; prints the numbers compared beside
their limits on standard error and one JSON object as the last line of
standard output.  This file names no model, cell or metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import config_io  # noqa: E402


def find_devices(chips, platform="tpu"):
    """The accelerator's devices, or exit: a measurement without the chips
    the cell asks for is refused, not made on something else."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < chips:
        print(f"run.py measures on {chips} {platform} chip(s); found "
              f"{len(devices)} x {devices[0].platform} "
              f"({devices[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return devices


def metric_reader(name):
    """``metrics/<name>.py``, or for a family split by suffix
    (``share.images``) the file of the part before the first dot."""
    for stem in (name, name.split(".")[0]):
        if os.path.exists(os.path.join(HERE, "metrics", stem + ".py")):
            return importlib.import_module("metrics." + stem).read
    raise LookupError(f"no reader metrics/{name}.py")


def cell_metrics(bench, group, workload, reported):
    """The metrics of ``group`` this cell has to report: those that list it
    under ``workloads``, and of those that list nothing every one whose
    end-to-end metric this cell reports."""
    out = []
    for m in bench[group]:
        listed = m.get("workloads")
        if listed is not None:
            if workload in listed:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = config_io.load_benchmark()
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = config_io.load_cell(entry["name"])
    cfg = config_io.load_config(entry["config"])
    devices = find_devices(int(entry["chips"]))
    peaks = config_io.load_json("peaks.json").get(devices[0].device_kind)
    if peaks is None:
        print(f"no peaks for device kind {devices[0].device_kind!r} in "
              "benchmarks/peaks.json", file=sys.stderr)
        return 2

    driver = importlib.import_module("drivers." + cell["driver"])
    run = driver.run(cell, cfg, args, T_START)
    run.update(peaks=peaks, chips=int(entry["chips"]))
    return report(bench, entry, run, devices, int(args.trace))


def report(bench, entry, run, devices, traced):
    e2e = run["end_to_end"]
    wanted = cell_metrics(bench, "end_to_end", entry["name"], e2e)
    metrics = {}
    if not traced:
        for m in wanted:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, "per_layer", entry["name"],
                              {w["name"] for w in wanted}):
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": int(entry["chips"]),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    trace = run.get("trace")
    if traced and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["measured"] = run["measured"]
    line["compared"] = run["compared"]
    for name, detail in run["details"].items():
        print(f"  {name}: {detail}", file=sys.stderr)
    for name, c in run["compared"].items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct = {run['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
