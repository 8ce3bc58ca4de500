"""moe_device_ms.<items>: the routed layers (units of class
``RoutedExpertsFFN``: route, dispatch, experts, shared experts, combine;
the buffers' ``conditional``s at their self time plus their branches'
operations), forward, recomputed forward and backward, in ms of device
self time a traced train step.  Source: the
profiler's trace joined to the program's scope tables
(unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    return unit_device_ms.of_classes(run, "RoutedExpertsFFN")
