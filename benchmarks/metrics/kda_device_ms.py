"""kda_device_ms.<items>: the Kimi delta-attention mixers (units of class
``KimiDeltaAttention``: projections, convolutions, decay and step, the
chunked rule, gated norm), forward and backward, in ms of device self
time a traced train step.  Source: the profiler's trace joined to the
program's scope tables (unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.of_classes(run, "KimiDeltaAttention")
