"""head_device_ms.<items>: the unit that feeds the evaluator (the
projection to the vocabulary) and the evaluator (loss and metrics),
forward and backward, in ms of device self time a traced train step.
Source: the profiler's trace joined to the program's scope tables
(unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.ms_a_step(
        run, lambda r: r["role"] in ("head", "evaluator"))
