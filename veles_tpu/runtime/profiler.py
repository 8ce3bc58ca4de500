"""On-demand device profiler capture: ``POST /debug/profile``.

Until now the only way to get a device-level trace out of a serving
process was to restart it with ``--profile DIR`` — which destroys the
very state (warm caches, live load, the slow request pattern) being
debugged.  This module wraps ``jax.profiler`` start/stop in a
duration-bounded, single-flight capture an operator can trigger over
HTTP against the RUNNING process (reference parity: the L10 per-unit
profiler was likewise a runtime toggle, ``--profile-units`` /
veles/units.py:805-817, not a relaunch).

Contract (docs/observability.md "On-demand profiler capture"):

* one capture at a time — a second ``POST`` while one runs answers
  **409** with the active capture's path (the profiler is process-
  global state; two concurrent ``start_trace`` calls would corrupt
  both traces);
* duration is bounded by ``root.common.observe.profile_max_s`` — a
  typo'd ``{"duration_s": 9999}`` must not profile the service into
  the ground;
* captures land under ``root.common.observe.profile_dir`` (default
  ``<cache_dir>/profiles``) in a per-capture timestamped directory,
  returned in the response and linked from the status page —
  TensorBoard/xprof-loadable.  Beside the trace files lies
  ``program_scopes.json``: the scope tables of the programs this
  process compiled (``runtime/program_scopes.py``), so the capture's
  device events can be summed by unit (docs/observability.md "Device
  time by unit").

Host-side only: the capture thread blocks in ``time.sleep``, never in
traced scope (VT103).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Optional, Tuple

from ..config import root
from ..logger import Logger
from . import program_scopes
from .metrics import ScopedCounter, registry

_CAPTURE_IDS = itertools.count(1)


class ProfilerBusy(RuntimeError):
    """A capture is already running (the HTTP 409 path)."""

    def __init__(self, path: str):
        super().__init__(
            f"a profiler capture is already running (writing {path}); "
            "retry when it finishes")
        self.path = path


class ProfilerCapture(Logger):
    """Single-flight ``jax.profiler`` capture driver (one per process
    behind :func:`profiler`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._active_path: Optional[str] = None  # guarded-by: self._lock
        self._last_path: Optional[str] = None    # guarded-by: self._lock
        # per-instance view over the shared registry series (the
        # engine's counter idiom): stats() and /metrics can never drift
        self._captures = ScopedCounter(registry().counter(
            "vt_profile_captures_total",
            "completed on-demand profiler captures "
            "(POST /debug/profile)"))  # guarded-by: self._lock

    @property
    def active(self) -> bool:
        with self._lock:
            return self._active_path is not None

    @property
    def last_path(self) -> Optional[str]:
        """Directory of the most recent finished capture (the status
        page links it)."""
        with self._lock:
            return self._last_path

    def _capture_dir(self, out_dir: Optional[str]) -> str:
        base = out_dir or str(
            root.common.observe.get("profile_dir", "") or "")
        if not base:
            base = os.path.join(str(root.common.cache_dir), "profiles")
        stamp = time.strftime("%Y%m%d-%H%M%S")
        return os.path.join(
            base, f"{stamp}-{os.getpid()}-{next(_CAPTURE_IDS):03d}")

    def capture(self, duration_s: float = 1.0,
                out_dir: Optional[str] = None) -> dict:
        """Run one duration-bounded device trace; blocks for the
        duration and returns ``{path, duration_s, files}``.  Raises
        :class:`ProfilerBusy` when a capture is already in flight."""
        cap = float(root.common.observe.get("profile_max_s", 30.0))
        dur = min(max(float(duration_s), 0.01), max(cap, 0.01))
        path = self._capture_dir(out_dir)
        with self._lock:
            if self._active_path is not None:
                raise ProfilerBusy(self._active_path)
            self._active_path = path
        try:
            os.makedirs(path, exist_ok=True)
            import jax
            self.info("profiler capture -> %s (%.2fs)", path, dur)
            jax.profiler.start_trace(path)
            try:
                time.sleep(dur)
            finally:
                jax.profiler.stop_trace()
            # the capture describes itself: which unit each instruction
            # of the noted programs belongs to, for the same join a
            # training run's trace gets (program_scopes.seconds_by_scope
            # over ScopeTable.from_json of each entry)
            with open(os.path.join(path, "program_scopes.json"), "w") as f:
                json.dump([t.to_json() for t in program_scopes.noted()], f)
            n_files = sum(len(fs) for _b, _d, fs in os.walk(path))
            with self._lock:
                self._last_path = path
                self._captures.inc()
            return {"path": path, "duration_s": dur, "files": n_files}
        finally:
            with self._lock:
                self._active_path = None

    def stats(self) -> dict:
        with self._lock:
            return {"active": self._active_path is not None,
                    "captures": self._captures.n,
                    "last_path": self._last_path}


def serve_profile_post(headers, rfile) -> Tuple[int, dict]:
    """The ONE HTTP half of ``POST /debug/profile`` both servers route
    to (StatusServer and RestfulServer must never drift on the ingress
    cap or the error mapping): body-size 413 before any read, negative
    Content-Length clamped (``rfile.read(-1)`` would block the handler
    thread until the client hangs up), JSON parse, capture, and the
    409/400/500 mapping.  Returns ``(status_code, json_body)``."""
    try:
        n = max(int(headers.get("Content-Length", 0) or 0), 0)
        cap = int(float(root.common.serve.get("max_body_mb", 64))
                  * 2 ** 20)
        if n > cap:
            # refuse BEFORE reading an unbounded body into memory
            return 413, {"error": f"request body {n} bytes exceeds "
                                  f"the {cap} byte cap "
                                  "(root.common.serve.max_body_mb)"}
        req = json.loads(rfile.read(n)) if n else {}
        # no client-chosen output path: captures stay confined to
        # root.common.observe.profile_dir
        return 200, profiler().capture(
            duration_s=float(req.get("duration_s", 1.0)))
    except ProfilerBusy as e:
        return 409, {"error": str(e), "active": e.path}
    except (TypeError, ValueError, json.JSONDecodeError) as e:
        return 400, {"error": str(e)}
    except Exception as e:  # noqa: BLE001 — capture failures answer
        return 500, {"error": f"{type(e).__name__}: {e}"}


_PROFILER_LOCK = threading.Lock()
_PROFILER: Optional[ProfilerCapture] = None  # guarded-by: _PROFILER_LOCK


def profiler() -> ProfilerCapture:
    """THE process capture driver (what ``POST /debug/profile`` runs)."""
    global _PROFILER
    with _PROFILER_LOCK:
        if _PROFILER is None:
            _PROFILER = ProfilerCapture()
        return _PROFILER
