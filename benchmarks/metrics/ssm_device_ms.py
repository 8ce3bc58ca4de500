"""ssm_device_ms.<items>: the state-space mixers (units of class
``Mamba2Mixer``: projections, convolution, scan, gated norm), forward and
backward, in ms of device self time a traced train step.  Source: the
profiler's trace joined to the program's scope tables
(unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.of_classes(run, "Mamba2Mixer")
