"""lrn_device_ms.<items>: the local response normalisations (units of
class ``LRN``; a fusion that holds a neighbour's work goes whole to the
unit XLA names it by), forward and backward, in ms of device self time a
traced train step.  Source: the
profiler's trace joined to the program's scope tables
(unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.of_classes(run, "LRN")
