"""scoped_device_share.<items>: how much of the chip's busy time the
program's scope tables explain, in %: the seconds of the rows that name
a unit (or ``optimizer``, ``loader_gather``, ``loader_aug``) over
``trace.busy_s``.  What is left is ``unscoped`` (an instruction XLA
gave no ``op_name``, or none that names a unit), ``ambiguous`` or
``unmatched`` (a program that was never noted).  Source: the profiler's
trace joined to the program's scope tables (unit_device_ms.py)."""

from metrics import unit_device_ms


def read(run):
    scopes = unit_device_ms.joined(run)
    busy = (run.get("trace") or {}).get("busy_s")
    if scopes is None or not busy:
        return None
    return 100.0 * scopes["scoped_s"] / busy
