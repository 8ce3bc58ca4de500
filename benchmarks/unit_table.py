"""A traced run of one cell that prints the device's time by unit,
whatever ``BENCHMARK.json`` lists the cell under::

    python3 benchmarks/unit_table.py --workload olmo_hybrid_train_t4096 \\
        --seed 7 --seconds 10

is ``benchmarks/run.py ... --trace 1`` (the same driver, the same result
line on standard output) with the table of
``metrics/unit_device_ms.py`` and every ``*_device_ms`` reader's value
on standard error first.  For a cell whose list of metrics a
``benchmark`` issue has not extended yet (``olmo_hybrid_train_t4096``
and ``gdn_device_ms``), and for a builder's chip session.
"""

import sys

import run
from metrics import unit_device_ms

READERS = ("ssm_device_ms", "gdn_device_ms", "moe_device_ms",
           "attn_device_ms", "head_device_ms", "lrn_device_ms",
           "optimizer_device_ms", "scoped_device_share")


def report(bench, entry, out, devices, traced, then=run.report):
    unit_device_ms.joined(out)      # prints the table, once a run
    for stem in READERS:
        value = run.metric_reader(stem)(out)
        if value is not None:
            print(f"{stem} = {value!r}", file=sys.stderr)
    return then(bench, entry, out, devices, traced)


def main(argv=None):
    run.report = report
    argv = list(sys.argv[1:] if argv is None else argv)
    return run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
