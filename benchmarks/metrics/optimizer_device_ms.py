"""optimizer_device_ms.<items>: the update (the scope ``optimizer``:
the optimizer's passes over parameters and slots with the sentinel's
norms and select), in ms of device self time a traced train step.  Source: the
profiler's trace joined to the program's scope tables
(unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.of_classes(run, "optimizer")
