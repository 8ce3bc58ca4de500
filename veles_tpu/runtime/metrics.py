"""Metrics core: Counter / Gauge / Histogram primitives in a
process-global registry, Prometheus text exposition, and a bounded span
ring exportable as a Chrome-trace / Perfetto JSON timeline.

Reference parity: the L10 observability stack (reference:
veles/web_status.py Tornado+MongoDB status, veles/logger.py:264 MongoDB
event tracing, veles/units.py:805-817 per-unit timing) sampled gauges
and logged events but never measured *distributions* — and neither did
this rebuild until now: the engine exposed a single ``tokens_per_sec``
gauge, so no perf PR could be judged against a tail-latency baseline.

Design rules (docs/observability.md "Metrics & tracing"):

* **zero dependencies** — stdlib only, no prometheus_client; the text
  format is ~40 lines to emit and every scraper speaks it (``span``
  alone reaches for ``jax.profiler``, when it is entered);
* **host-side only** — nothing here may be called from traced scope
  (the analyzer's VT103 rule enforces it: ``time``/IO inside a traced
  program is flagged at lint time);
* **small-cardinality labels** — label sets are bounded per metric
  (``root.common.observe.label_cap``); past the cap new label values
  collapse into a single ``_other`` series and are counted in
  ``vt_metrics_dropped_labels_total``, because an unbounded label value
  (e.g. a request id) turns a metrics page into a memory leak;
* **fixed buckets** — histograms are fixed-bucket (Prometheus
  semantics: cumulative ``_bucket{le=...}`` counts + ``_sum`` +
  ``_count``), so merging across processes and computing quantiles
  after the fact both stay trivial;
* **one registry** — the ad-hoc gauges (``engine.stats()``, StepCache
  compile counters, deploy swap history) feed the SAME registry the
  ``/metrics`` endpoint renders, so status.json, ``GET /engine`` and
  ``GET /metrics`` present one consistent view.

The span ring is the request-level half: bounded (``root.common
.observe.span_ring``), host-timestamped spans — per-request serving
timelines (queue-wait → prefill → decode), the trainer's ``train_run``
tree (:class:`span`), status events as instants — served as
``GET /trace.json`` and written by ``--trace-out``, loadable directly in
Perfetto / ``chrome://tracing``.
"""

from __future__ import annotations

import collections
import itertools
import json
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..config import root
from ..logger import event_tracer

#: default latency buckets (seconds): sub-ms prefills on warm caches up
#: to the engine's 60s retry ceiling; chosen so TTFT, queue-wait and
#: decode-step distributions all land mid-range instead of saturating
#: an end bucket.
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

#: the label-set a metric past its cardinality cap collapses into.
OVERFLOW_LABEL = "_other"


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return (s.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(v) -> str:
    """Prometheus sample value: integral floats render as ints (bucket
    counts), the rest as shortest-repr floats."""
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, float):
        if v != v:          # NaN
            return "NaN"
        if v in (float("inf"), float("-inf")):
            return "+Inf" if v > 0 else "-Inf"
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v)
    return str(v)


class _Metric:
    """Shared parent for the three kinds: owns the name, help text,
    label names, and the children table (one child per label-value
    tuple; the empty tuple is the label-less default child)."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Tuple[str, ...],
                 label_cap: int):
        if not re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name):
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self.label_cap = max(1, int(label_cap))
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}  # guarded-by: self._lock
        self._dropped = None        # registry's overflow counter child
        if not self.labelnames:
            with self._lock:
                self._children[()] = self._make_child()

    def _make_child(self):
        raise NotImplementedError

    def labels(self, **kv):
        """The child series for one label-value assignment.  Values are
        stringified; an unseen assignment past the cardinality cap
        collapses into the ``_other`` series (and is counted) instead
        of growing the table without bound."""
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name} takes labels {self.labelnames}, "
                f"got {tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if len(self._children) >= self.label_cap:
                    key = (OVERFLOW_LABEL,) * len(self.labelnames)
                    child = self._children.get(key)
                    if child is None:
                        child = self._children[key] = self._make_child()
                    dropped = self._dropped
                else:
                    child = self._children[key] = self._make_child()
                    dropped = None
            else:
                dropped = None
        if dropped is not None:
            dropped.inc()
        return child

    def _default(self):
        if self.labelnames:
            raise ValueError(
                f"{self.name} is labelled {self.labelnames}; "
                "call .labels(...) first")
        with self._lock:
            return self._children[()]

    def series_count(self) -> int:
        with self._lock:
            return len(self._children)

    def _snapshot(self) -> List[Tuple[Tuple[str, ...], object]]:
        with self._lock:
            return list(self._children.items())


class _CounterChild:
    __slots__ = ("_lock", "_v")

    def __init__(self, lock):
        self._lock = lock
        self._v = 0.0  # guarded-by: self._lock

    def inc(self, amount: float = 1.0):
        if amount < 0:
            raise ValueError("counters only go up; use a Gauge")
        with self._lock:
            self._v += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._v


class Counter(_Metric):
    """Monotonic count.  ``inc()`` on the label-less default, or
    ``labels(outcome="ok").inc()``."""

    kind = "counter"

    def _make_child(self):
        return _CounterChild(self._lock)

    def inc(self, amount: float = 1.0):
        self._default().inc(amount)

    @property
    def value(self) -> float:
        return self._default().value


class _GaugeChild:
    __slots__ = ("_lock", "_v")

    def __init__(self, lock):
        self._lock = lock
        self._v = 0.0  # guarded-by: self._lock

    def set(self, v: float):
        with self._lock:
            self._v = float(v)

    def inc(self, amount: float = 1.0):
        with self._lock:
            self._v += amount

    def dec(self, amount: float = 1.0):
        self.inc(-amount)

    @property
    def value(self) -> float:
        with self._lock:
            return self._v


class Gauge(_Metric):
    """Point-in-time value; ``set()`` wins over inc/dec for sampled
    gauges (occupancy, queue depth)."""

    kind = "gauge"

    def _make_child(self):
        return _GaugeChild(self._lock)

    def set(self, v: float):
        self._default().set(v)

    def inc(self, amount: float = 1.0):
        self._default().inc(amount)

    def dec(self, amount: float = 1.0):
        self._default().dec(amount)

    @property
    def value(self) -> float:
        return self._default().value


class _HistogramChild:
    __slots__ = ("_lock", "uppers", "_counts", "_sum", "_count")

    def __init__(self, lock, uppers):
        self._lock = lock
        self.uppers = uppers            # finite upper bounds, ascending
        self._counts = [0] * (len(uppers) + 1)  # guarded-by: self._lock
        self._sum = 0.0  # guarded-by: self._lock
        self._count = 0  # guarded-by: self._lock

    def observe(self, v: float):
        v = float(v)
        # linear scan: bucket lists are ~16 long and the scan is
        # lock-held for nanoseconds; bisect would save nothing
        i = 0
        for u in self.uppers:
            if v <= u:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> Tuple[List[int], float, int]:
        """(per-bucket counts incl. +Inf, sum, count) — one consistent
        view under the lock."""
        with self._lock:
            return list(self._counts), self._sum, self._count

    def cumulative(self) -> List[Tuple[float, float]]:
        """[(le, cumulative_count)] including the +Inf bucket."""
        counts, _, _ = self.snapshot()
        out, acc = [], 0
        for u, c in zip(self.uppers, counts):
            acc += c
            out.append((u, float(acc)))
        out.append((float("inf"), float(acc + counts[-1])))
        return out

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def quantile(self, q: float) -> float:
        return quantile_from_cumulative(self.cumulative(), q)


class Histogram(_Metric):
    """Fixed-bucket latency/size distribution with Prometheus
    cumulative-bucket exposition and host-side quantile estimation
    (linear interpolation inside the target bucket — the same estimate
    ``histogram_quantile`` computes server-side)."""

    kind = "histogram"

    def __init__(self, name, help, labelnames, label_cap,
                 buckets=DEFAULT_BUCKETS):
        uppers = tuple(sorted(float(b) for b in buckets))
        if not uppers:
            raise ValueError("histogram needs at least one bucket")
        if uppers[-1] == float("inf"):
            uppers = uppers[:-1]        # +Inf is implicit
        self.buckets = uppers
        super().__init__(name, help, labelnames, label_cap)

    def _make_child(self):
        return _HistogramChild(self._lock, self.buckets)

    def observe(self, v: float):
        self._default().observe(v)

    @property
    def count(self) -> int:
        return self._default().count

    @property
    def sum(self) -> float:
        return self._default().sum

    def quantile(self, q: float) -> float:
        return self._default().quantile(q)

    def aggregate_snapshot(self) -> Tuple[List[int], float, int]:
        """(per-bucket counts incl. +Inf, sum, count) summed across
        every child series — the label-blind view a rolling SLO window
        snapshots (children share one bucket layout by construction)."""
        counts = [0] * (len(self.buckets) + 1)
        total, n = 0.0, 0
        for _key, child in self._snapshot():
            c, s, cnt = child.snapshot()
            for i, v in enumerate(c):
                counts[i] += v
            total += s
            n += cnt
        return counts, total, n


class MetricsRegistry:
    """Named metrics in registration order.  Registration is
    idempotent: re-registering an existing name returns the existing
    metric (modules register at construction time and engines/trainers
    are built many times per process) — but a kind/label mismatch is a
    loud error, never a silent shadow."""

    def __init__(self, label_cap: Optional[int] = None):
        self._lock = threading.Lock()
        self._metrics: "collections.OrderedDict[str, _Metric]" = \
            collections.OrderedDict()  # guarded-by: self._lock
        self._label_cap = label_cap
        self.dropped_labels = self.counter(
            "vt_metrics_dropped_labels_total",
            "label assignments collapsed into the _other series by the "
            "per-metric cardinality cap (root.common.observe.label_cap)")

    def _cap(self) -> int:
        if self._label_cap is not None:
            return self._label_cap
        return int(root.common.observe.get("label_cap", 64))

    def _register(self, cls, name, help, labels, **kw) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls) or m.labelnames != tuple(labels):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{m.kind} with labels {m.labelnames}")
                return m
            m = cls(name, help, tuple(labels), self._cap(), **kw)
            m._dropped = getattr(self, "dropped_labels", None)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str,
                labels: Tuple[str, ...] = ()) -> Counter:
        return self._register(Counter, name, help, labels)

    def gauge(self, name: str, help: str,
              labels: Tuple[str, ...] = ()) -> Gauge:
        return self._register(Gauge, name, help, labels)

    def histogram(self, name: str, help: str,
                  labels: Tuple[str, ...] = (),
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, name, help, labels,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def _ordered(self) -> List[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4: ``# HELP`` /
        ``# TYPE`` per metric, one sample line per child series,
        histograms as cumulative ``_bucket{le=...}`` + ``_sum`` +
        ``_count``."""
        lines: List[str] = []
        for m in self._ordered():
            lines.append(f"# HELP {m.name} {_escape_help(m.help)}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for key, child in m._snapshot():
                pairs = [f'{n}="{_escape_label(v)}"'
                         for n, v in zip(m.labelnames, key)]
                if m.kind == "histogram":
                    base = ",".join(pairs)
                    acc = 0
                    counts, total, count = child.snapshot()
                    for u, c in zip(m.buckets, counts):
                        acc += c
                        lab = base + ("," if base else "") \
                            + f'le="{_fmt(u)}"'
                        lines.append(f"{m.name}_bucket{{{lab}}} {acc}")
                    lab = base + ("," if base else "") + 'le="+Inf"'
                    lines.append(
                        f"{m.name}_bucket{{{lab}}} {acc + counts[-1]}")
                    suffix = f"{{{base}}}" if base else ""
                    lines.append(f"{m.name}_sum{suffix} {_fmt(total)}")
                    lines.append(f"{m.name}_count{suffix} {count}")
                else:
                    suffix = f"{{{','.join(pairs)}}}" if pairs else ""
                    lines.append(
                        f"{m.name}{suffix} {_fmt(child.value)}")
        return "\n".join(lines) + "\n"


class HistogramWindow:
    """Time-windowed view over a cumulative histogram: a bounded ring of
    bucket snapshots taken at slice boundaries, so quantiles over "the
    last ``window_s`` seconds" come out of the same fixed buckets the
    since-boot series exposes (a cumulative histogram hides a fresh
    regression behind hours of good history — the SLO problem,
    docs/observability.md "Rolling SLO windows").

    ``source`` is a zero-arg callable returning the Histogram (or None
    before it is registered) — late binding keeps this module free of
    any registration-order coupling.  Rotation is lazy: every read (or
    an explicit :meth:`tick`) appends a snapshot once a slice elapsed,
    so a cheap ticker — the decode scheduler tick, the SLO ticker
    thread — keeps the ring honest and an idle process pays nothing.
    ``clock`` is injectable for deterministic tests."""

    def __init__(self, source, window_s: float, slices: int = 12,
                 clock=time.monotonic):
        self._source = source
        self.window_s = max(float(window_s), 1e-9)
        self.slices = max(int(slices), 1)
        self.slice_s = self.window_s / self.slices
        self._clock = clock
        self._lock = threading.Lock()
        # ring of (t, cumulative counts incl. +Inf, sum, count); one
        # extra slot keeps a baseline just outside the window
        self._ring: collections.deque = collections.deque(
            maxlen=self.slices + 1)  # guarded-by: self._lock

    def _snap(self):
        hist = self._source()
        if hist is None:
            return None, [0], 0.0, 0
        counts, total, n = hist.aggregate_snapshot()
        return hist, counts, total, n

    def tick(self) -> bool:
        """Rotate if a slice boundary passed (idempotent; the no-op
        path is one clock read + a deque peek).  Returns whether a
        snapshot was appended — callers refresh derived gauges only on
        rotation."""
        now = self._clock()
        with self._lock:
            if self._ring and now - self._ring[-1][0] < self.slice_s:
                return False
            _hist, counts, total, n = self._snap()
            self._ring.append((now, counts, total, n))
            return True

    def delta(self):
        """(histogram, cumulative ``(le, count)`` pairs, count, sum) of
        the observations inside the window: current state minus the
        newest snapshot at least ``window_s`` old (or the oldest held —
        a young ring covers less than the full window, never more)."""
        self.tick()
        now = self._clock()
        hist, counts, total, n = self._snap()
        if hist is None:
            return None, [], 0, 0.0
        base = None
        with self._lock:
            for t, c, s, cnt in self._ring:
                if base is None or t <= now - self.window_s:
                    base = (c, s, cnt)
        bc, bs, bn = base if base is not None \
            else ([0] * len(counts), 0.0, 0)
        if len(bc) != len(counts):      # ring predates the registration
            bc = [0] * len(counts)
        pairs, acc = [], 0
        for u, cur, old in zip(hist.buckets, counts, bc):
            acc += cur - old
            pairs.append((u, float(acc)))
        pairs.append((float("inf"),
                      float(acc + counts[-1] - bc[-1])))
        return hist, pairs, n - bn, total - bs

    def quantile(self, q: float) -> float:
        _hist, pairs, _n, _s = self.delta()
        return quantile_from_cumulative(pairs, q)

    def summary(self, quantiles=(0.5, 0.95, 0.99)) -> dict:
        """Windowed count / sum / quantiles in one consistent read."""
        _hist, pairs, n, s = self.delta()
        out = {"count": int(n), "sum": round(s, 6)}
        for q in quantiles:
            out[f"p{int(q * 100)}"] = quantile_from_cumulative(pairs, q)
        return out


class ScopedCounter:
    """A per-instance view over a shared registry counter series: every
    ``inc()`` feeds the process-global Prometheus series, while ``n``
    counts THIS instance's increments — so ``engine.stats()`` on a
    fresh engine still starts at zero even though the registry series
    (which outlives engines) does not reset.  ``n``'s own thread
    discipline is the caller's, exactly as it was for the plain ints
    these replace."""

    __slots__ = ("_child", "n")

    def __init__(self, child):
        self._child = child
        self.n = 0

    def inc(self, amount: int = 1):
        self.n += amount
        self._child.inc(amount)


# -- the process-global registry --------------------------------------------

_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """THE process registry: everything ``GET /metrics`` renders."""
    return _REGISTRY


# -- span ring: request/step timelines as Chrome-trace JSON ------------------

#: monotonic origin for trace timestamps (Chrome trace ``ts`` is in
#: microseconds; an absolute epoch would overflow the viewer's slider).
_T0 = time.monotonic()

_TRACE_IDS = itertools.count(1)


def next_trace_id() -> int:
    """Process-unique track id for a request timeline (``next`` on an
    itertools.count is atomic under the GIL)."""
    return next(_TRACE_IDS)


def _us(t: float) -> float:
    return round((t - _T0) * 1e6, 1)


class SpanRing:
    """Bounded ring of completed host-side spans in Chrome trace event
    format.  Bounded because it lives for the process: a serving day at
    qps keeps only the most recent ``capacity`` spans, which is exactly
    the window an operator pulls when something is slow NOW."""

    def __init__(self, capacity: int = 512):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))  # guarded-by: self._lock

    def add(self, name: str, start_s: float, dur_s: float, *,
            cat: str = "host", tid: int = 0, args: Optional[dict] = None):
        """One complete ("X") span: ``start_s``/``dur_s`` in
        ``time.monotonic()`` seconds."""
        ev = {"name": str(name), "cat": cat, "ph": "X",
              "ts": _us(start_s), "dur": round(max(dur_s, 0.0) * 1e6, 1),
              "pid": 0, "tid": int(tid)}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def add_instant(self, name: str, at_s: float, *, cat: str = "event",
                    tid: int = 0, args: Optional[dict] = None):
        ev = {"name": str(name), "cat": cat, "ph": "i", "s": "g",
              "ts": _us(at_s), "pid": 0, "tid": int(tid)}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def snapshot(self) -> List[dict]:
        with self._lock:
            return sorted(self._events, key=lambda e: e["ts"])

    def chrome_trace(self) -> dict:
        """The ``chrome://tracing`` / Perfetto document (also the
        ``GET /trace.json`` body)."""
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "veles_tpu"}}]
        return {"traceEvents": meta + self.snapshot(),
                "displayTimeUnit": "ms"}


_SPANS_LOCK = threading.Lock()
_SPANS: Optional[SpanRing] = None  # guarded-by: _SPANS_LOCK


def span_ring() -> SpanRing:
    """The process span ring, sized by ``root.common.observe.span_ring``
    at first use."""
    global _SPANS
    with _SPANS_LOCK:
        if _SPANS is None:
            _SPANS = SpanRing(
                int(root.common.observe.get("span_ring", 512)))
        return _SPANS


_SPAN_IDS = itertools.count(1)


class _OpenSpans(threading.local):
    """The spans each thread has open, innermost last."""

    def __init__(self):
        self.stack: List["span"] = []


_OPEN = _OpenSpans()


class span:
    """``with span("eval", klass="validation") as sp:`` — THE host-side
    span entry point: one call site puts the interval on three
    timelines.

    * the **span ring**: on exit one complete event whose args carry
      ``id``, ``parent`` (the enclosing span on this thread, or None)
      and ``trace`` (a root span draws a new ``next_trace_id()``;
      children inherit it), so a reader rebuilds the tree without
      comparing timestamps.  ``tid`` is the trace id, as the engine's
      request spans have it: one track per ``Trainer.run()``;
    * the **profiler**: a ``jax.profiler.TraceAnnotation`` of the same
      name and args, so the span lies on the host plane of any active
      capture, on the device trace's clock (a flag test otherwise);
    * the **JSONL timeline**: a begin/end pair, when
      ``root.common.trace_file`` is set.

    ``sp.args`` may be added to inside the body (totals known only at
    the end); ``sp.seconds`` holds the duration after exit.  An
    exception in the body still closes the span (``error`` in its
    args) and propagates."""

    def __init__(self, name: str, cat: str = "host", **args):
        self.name = str(name)
        self.cat = cat
        self.args = args
        self.id = next(_SPAN_IDS)
        self.parent: Optional[int] = None
        self.trace: Optional[int] = None
        self.seconds = 0.0

    def _ids(self) -> dict:
        return {"id": self.id, "parent": self.parent, "trace": self.trace}

    def __enter__(self):
        import jax.profiler
        stack = _OPEN.stack
        if stack:
            self.parent, self.trace = stack[-1].id, stack[-1].trace
        else:
            self.trace = next_trace_id()
        stack.append(self)
        event_tracer().emit(self.name, "begin", **self.args, **self._ids())
        self._annotation = jax.profiler.TraceAnnotation(
            self.name, **self.args)
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.monotonic() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        _OPEN.stack.pop()
        args = {**self.args, **self._ids()}
        if exc_type is not None:
            args["error"] = exc_type.__name__
        span_ring().add(self.name, self._t0, self.seconds, cat=self.cat,
                        tid=self.trace, args=args)
        event_tracer().emit(self.name, "end", seconds=self.seconds, **args)
        return False


def write_chrome_trace(path: str) -> str:
    """``--trace-out FILE``: dump the current span ring as Chrome-trace
    JSON (open in Perfetto: ui.perfetto.dev → Open trace file)."""
    with open(path, "w") as f:
        json.dump(span_ring().chrome_trace(), f, default=repr)
    return path


# -- scrape-side helpers (bench_serving.py, tests) ---------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_UNESCAPE_RE = re.compile(r"\\(.)")
_UNESCAPES = {"n": "\n", '"': '"', "\\": "\\"}


def _unescape_label(s: str) -> str:
    """Single-pass inverse of :func:`_escape_label` — sequential
    ``str.replace`` calls would corrupt a value holding a literal
    backslash before an 'n' (``\\\\n`` is backslash+n, not newline)."""
    return _UNESCAPE_RE.sub(
        lambda m: _UNESCAPES.get(m.group(1), "\\" + m.group(1)), s)


def parse_samples(text: str) -> List[Tuple[str, Dict[str, str], float]]:
    """Parse Prometheus exposition text into ``(name, labels, value)``
    sample tuples — the scrape half the bench uses to turn a
    ``/metrics`` body back into numbers."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, raw_labels, raw_v = m.groups()
        labels = {}
        for k, v in _LABEL_RE.findall(raw_labels or ""):
            labels[k] = _unescape_label(v)
        try:
            out.append((name, labels, float(raw_v)))
        except ValueError:
            continue
    return out


def cumulative_buckets(samples, name: str) -> List[Tuple[float, float]]:
    """Aggregate a histogram's ``_bucket`` samples (summing across any
    non-``le`` labels) into sorted ``[(le, cumulative_count)]``."""
    agg: Dict[float, float] = {}
    for n, labels, v in samples:
        if n != name + "_bucket" or "le" not in labels:
            continue
        le = float(labels["le"])
        agg[le] = agg.get(le, 0.0) + v
    return sorted(agg.items())


def delta_buckets(before, after) -> List[Tuple[float, float]]:
    """Cumulative-bucket difference of two scrapes — how a bench
    isolates one scenario's distribution on the process-global
    registry."""
    base = dict(before)
    return [(le, c - base.get(le, 0.0)) for le, c in after]


def fraction_over(pairs, threshold: float) -> float:
    """Fraction of observations above ``threshold`` from cumulative
    ``(le, count)`` pairs, interpolating linearly inside the bucket the
    threshold lands in (the same estimate the quantile helper inverts)
    — the burn-rate numerator of the rolling SLO windows."""
    pairs = sorted(pairs)
    if not pairs or pairs[-1][1] <= 0:
        return 0.0
    total = pairs[-1][1]
    prev_le, prev_c = 0.0, 0.0
    for le, c in pairs:
        if threshold <= le:
            if le == float("inf"):
                return (total - prev_c) / total
            width = le - prev_le
            frac = (threshold - prev_le) / width if width > 0 else 1.0
            at = prev_c + frac * (c - prev_c)
            return max(0.0, (total - at) / total)
        prev_le, prev_c = le, c
    return 0.0


def quantile_from_cumulative(pairs, q: float) -> float:
    """Quantile estimate from cumulative ``(le, count)`` pairs: linear
    interpolation inside the target bucket, the last finite bound for
    the +Inf bucket (Prometheus ``histogram_quantile`` semantics)."""
    pairs = sorted(pairs)
    if not pairs or pairs[-1][1] <= 0:
        return 0.0
    q = min(max(float(q), 0.0), 1.0)
    target = q * pairs[-1][1]
    prev_le, prev_c = 0.0, 0.0
    for le, c in pairs:
        if c >= target:
            if le == float("inf"):
                return prev_le
            width_c = c - prev_c
            frac = (target - prev_c) / width_c if width_c > 0 else 1.0
            return prev_le + frac * (le - prev_le)
        if le != float("inf"):
            prev_le = le
        prev_c = c
    return prev_le
