"""Per-device op-variant autotuning with a persisted winner DB.

Reference parity: veles/backends.py:672-731 — the OpenCL backend swept
gemm block sizes (3 reps, size 3001) per device and persisted the winner
to ``devices/device_infos.json``, reused on every later run. Generalized
here for the TPU build: an op with several mathematically-equivalent
formulations asks :func:`pick` for the measured winner on THIS device
for THIS shape class; winners persist under the ``autotune`` key of the
same per-device-kind DB the gemm benchmark uses
(``runtime/benchmark.py``).  One op does (docs/autotune.md): attention,
flash kernel and block shape against XLA (``MultiHeadAttention.prepare``).
Every other Pallas-vs-XLA choice follows ``ops.use_pallas_default``.

Measurement methodology: repetitions are chained INSIDE one jit with an
``optimization_barrier`` and a denormal feedback term, so the launch
cost is amortized and XLA can neither fold repetitions nor skip
materializing outputs (a harness without the barrier once mis-decided
two kernel defaults — BASELINE.md).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Mapping, Optional, Sequence

from ..config import root
from ..logger import Logger
from .benchmark import (device_info_path, load_device_infos,
                        update_device_info)

class _AutotuneLog(Logger):
    pass


_log = _AutotuneLog()

# In-process memo so one run never re-reads the DB (or re-measures) for
# the same decision twice.
_memo: Dict[str, str] = {}


def _shape_key(args: Sequence) -> str:
    parts = []
    for a in args:
        shape = tuple(getattr(a, "shape", ()) or ())
        dtype = getattr(a, "dtype", None)
        parts.append(f"{'x'.join(map(str, shape))}:{dtype}")
    return ",".join(parts)


def measure(fn: Callable, args: Sequence, reps: int = 4,
            iters: int = 3) -> float:
    """Per-call seconds for fn(*args), reps chained in-graph."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # Chain the inter-rep data dependence through the SMALLEST argument
    # so the chain edge itself is nearly free (threading it through a
    # large operand would add a full HBM pass per repetition).
    j = int(np.argmin([int(np.prod(getattr(a, "shape", ()) or (1,)))
                       for a in args]))

    def chained(*args):
        out = fn(*args)
        for _ in range(reps - 1):
            out = jax.lax.optimization_barrier(out)
            leaf = jax.tree.leaves(out)[0]
            eps = jnp.sum(leaf.astype(jnp.float32)) * 1e-38
            args = list(args)
            args[j] = args[j] + eps.astype(args[j].dtype)
            out = fn(*args)
        return out

    cf = jax.jit(chained)
    jax.block_until_ready(cf(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = cf(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (iters * reps)


def lookup(op: str, names: Sequence[str], args: Sequence,
           cache_dir: Optional[str] = None) -> Optional[str]:
    """Winner for ``op`` from memo/DB only — never measures. Returns
    None when no valid record for this candidate set exists. Lets
    callers skip building measurement inputs entirely on warm starts
    (e.g. the loader's sample pack)."""
    import jax

    kind = jax.devices()[0].device_kind
    key = f"{op}|{_shape_key(args)}"
    memo_key = f"{device_info_path(cache_dir)}|{kind}|{key}"
    if memo_key in _memo and _memo[memo_key] in names:
        return _memo[memo_key]
    try:
        infos = load_device_infos(cache_dir)
    except (OSError, ValueError):  # unreadable DB: measure afresh
        return None
    rec = infos.get(kind, {}).get("autotune", {}).get(key)
    if (rec and rec.get("winner") in names
            and set(rec.get("ms", ())) == set(names)):
        _memo[memo_key] = rec["winner"]
        return rec["winner"]
    return None


def pick(op: str, candidates: Mapping[str, Callable], args: Sequence,
         default: Optional[str] = None, cache_dir: Optional[str] = None,
         refresh: bool = False) -> str:
    """Name of the fastest candidate for ``op`` on the current device.

    Measured at most once per (device kind, op, arg shapes/dtypes);
    afterwards answered from the in-process memo or the persisted DB.
    ``default`` answers only when autotuning is switched off.  A
    candidate that fails to compile or run raises, naming the candidate:
    a formulation the device refuses is a defect to repair, not a
    reason to run another one quietly.
    """
    import jax

    names = list(candidates)
    if default is None:
        default = names[0]
    if len(names) == 1:
        return names[0]
    if not bool(root.common.autotune):
        return default

    kind = jax.devices()[0].device_kind
    key = f"{op}|{_shape_key(args)}"
    # cache_dir in the memo key: callers mixing explicit and default DBs
    # must not receive each other's winners
    memo_key = f"{device_info_path(cache_dir)}|{kind}|{key}"
    if not refresh:
        cached = lookup(op, names, args, cache_dir)
        if cached is not None:
            return cached

    timings = {}
    for name in names:
        try:
            timings[name] = measure(candidates[name], args)
        except Exception as e:
            raise RuntimeError(
                f"autotune {op}: candidate {name!r} failed to compile or "
                f"run on {kind} ({type(e).__name__}: {e})") from e

    winner = min(timings, key=timings.get)
    _log.info("autotune %s on %s: %s  -> %s", op, kind,
              {k: f"{v * 1e3:.3f}ms" for k, v in timings.items()}, winner)
    record = {"winner": winner,
              "ms": {k: round(v * 1e3, 4) for k, v in timings.items()}}
    try:
        update_device_info(
            kind, lambda rec: rec.setdefault("autotune", {})
            .__setitem__(key, record), cache_dir)
    except OSError as e:  # read-only cwd etc. — the memo still holds
        _log.warning("autotune DB not persisted: %s", e)
    _memo[memo_key] = winner
    return winner
