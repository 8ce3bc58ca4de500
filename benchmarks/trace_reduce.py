"""From a profiler trace (``.xplane.pb``) to busy and idle seconds, time by
operation, and the longest idle gaps.

The arithmetic works on plain ``(name, start, end)`` tuples and is tested
on hand-made intervals; only ``read_xplane`` touches the trace's format.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

#: the line of a device's plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
DEVICE_PLANE = "/device:TPU:"
BOUNDARY = "epoch_boundary"


def merge(intervals):
    """Sorted, disjoint ``[start, end]`` lists covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, lo, hi):
    """The parts of ``(name, start, end)`` events inside ``[lo, hi]``."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_seconds(events):
    """Length of the union of the events' intervals."""
    return sum(e - s for s, e in merge((s, e) for _, s, e in events))


def seconds_by_name(events):
    out = {}
    for n, s, e in events:
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def idle_gaps(events, lo, hi, marks=()):
    """``[(label, seconds)]`` of the stretches of ``[lo, hi]`` in which no
    event runs, longest first.  A gap that holds the start of a marked
    span ``(name, start, end)`` takes that span's name, the others are
    "unattributed"."""
    gaps, at = [], lo
    for s, e in merge((s, e) for _, s, e in events) + [[hi, hi]]:
        if s > at:
            label = next((n for n, ms, me in marks
                          if ms < s and me > at), "unattributed")
            gaps.append((label, s - at))
        at = max(at, e)
    return sorted(gaps, key=lambda g: -g[1])


def short(name, width=160):
    """An operation's name as the trace gives it, without the layout
    annotations in braces, cut to ``width``."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def top(pairs, n=10):
    return [[short(name), secs] for name, secs in
            sorted(pairs, key=lambda p: -p[1])[:n]]


def reduce(device_events, marks, chips):
    """``device_events``: one list of ``(name, start, end)`` per chip, in
    seconds on the trace's clock.  ``marks``: the host's ``epoch_boundary``
    spans.  The window is from the end of the first boundary to the end of
    the last, whole epochs; with fewer than two, from the first operation
    to the last."""
    device_events = [ev for ev in device_events if ev]
    if not device_events:
        return None
    if len(device_events) != chips:
        raise RuntimeError(f"trace holds operations of {len(device_events)} "
                           f"devices, the cell uses {chips}")
    ends = sorted(me for _, _, me in marks)
    if len(ends) >= 2:
        lo, hi = ends[0], ends[-1]
    else:
        lo = min(s for ev in device_events for _, s, _ in ev)
        hi = max(e for ev in device_events for _, _, e in ev)
    per_chip = [clip(ev, lo, hi) for ev in device_events]
    busy = sum(busy_seconds(ev) for ev in per_chip) / len(per_chip)
    by_name = {}
    for ev in per_chip:
        for n, secs in seconds_by_name(ev).items():
            by_name[n] = by_name.get(n, 0.0) + secs / len(per_chip)
    inside = [m for m in marks if m[2] > lo and m[1] < hi]
    return {
        "busy_s": busy, "window_s": hi - lo,
        "epochs_in_window": max(len(ends) - 1, 0),
        "seconds_by_op": by_name,
        "device_ops": top(by_name.items()),
        "idle_gaps": top(idle_gaps(per_chip[0], lo, hi, inside)),
    }


def read_xplane(path):
    """(per-device lists of operation events, ``epoch_boundary`` spans),
    times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, marks = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks += [(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9)
                          for ev in line.events if ev.name == BOUNDARY]
    return devices, sorted(marks, key=lambda m: m[1])


def reduce_directory(directory, chips):
    """Reduce the newest trace under ``directory`` and delete the trace
    files (they are large, and the machine keeps what was written)."""
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    devices, marks = read_xplane(paths[-1])
    shutil.rmtree(directory, ignore_errors=True)
    return reduce(devices, marks, chips)
