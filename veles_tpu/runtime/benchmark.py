"""Device benchmark & compute-power rating.

Reference parity: the gemm DeviceBenchmark unit
(veles/accelerated_units.py:706-824) served two roles — (a) OpenCL
block-size autotuning persisted to ``devices/device_infos.json``
(veles/backends.py:672-731), (b) a slave ``computing_power`` rating
(1000/gemm-time, veles/accelerated_units.py:843-858) used by the master for
load balancing (veles/client.py:308-313).

TPU redesign: XLA owns tiling, so (a) becomes a *measurement* sweep —
gemm wall time / achieved TFLOPS per (size, dtype), persisted per device
kind (the analog of the device-info DB).  (b) survives as the same scalar
rating so higher layers (ensemble/GA job farming) can weight hosts by
throughput.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Sequence

import numpy as np

from ..config import root
from ..logger import Logger

# Reference benchmarked one size=3001 gemm (veles/backends.py:695: dtype
# sweep at size 3001); we sweep MXU-aligned sizes instead.
DEFAULT_SIZES = (1024, 2048, 4096)
DEFAULT_DTYPES = ("float32", "bfloat16")


#: Published per-chip peaks keyed by ``device_kind`` as JAX reports it:
#: the ONE denominator table for every MFU, bandwidth-utilization and
#: roofline figure in the repo (trainer MFU gauge, engine ``decode_mbu``,
#: ``bench_lm.py``).  A TPU kind that is not here is an error, never a
#: default; off a TPU there is no peak and the figure is "not measured".
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "tflops_bf16": 197.0, "hbm_gbps": 819.0,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16, 16 GB HBM2e at 819 GB/s per chip"},
}


def device_peaks(device=None) -> Optional[Dict]:
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: the first
    one); None off a TPU; ``LookupError`` for a TPU kind without a row."""
    import jax
    dev = device if device is not None else jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in DEVICE_PEAKS:
        raise LookupError(
            f"no published peaks for TPU device_kind {dev.device_kind!r}: "
            "add a row with its source to DEVICE_PEAKS "
            "(veles_tpu/runtime/benchmark.py)")
    return DEVICE_PEAKS[dev.device_kind]


def _gemm_seconds(n: int, dtype: str, reps: int) -> float:
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)),
                    jnp.dtype(dtype))

    @jax.jit
    def gemm_chain(a, b, k):
        # k dependent gemms per dispatch, so the launch cost amortizes
        def body(_, acc):
            return acc @ b
        return jax.lax.fori_loop(0, k, body, a)

    chain = 128
    gemm_chain(x, x, chain).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        gemm_chain(x, x, chain).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / chain)
    return best


class DeviceBenchmark(Logger):
    """Measure gemm throughput on the current default device."""

    def __init__(self, sizes: Sequence[int] = DEFAULT_SIZES,
                 dtypes: Sequence[str] = DEFAULT_DTYPES, reps: int = 3):
        self.sizes = tuple(sizes)
        self.dtypes = tuple(dtypes)
        self.reps = reps

    def run(self) -> Dict:
        import jax
        dev = jax.devices()[0]
        entries = []
        for dtype in self.dtypes:
            for n in self.sizes:
                secs = _gemm_seconds(n, dtype, self.reps)
                tflops = 2.0 * n ** 3 / secs / 1e12
                entries.append({"size": n, "dtype": dtype,
                                "seconds": secs, "tflops": tflops})
                self.info("gemm %dx%d %s: %.3f ms, %.2f TFLOPS",
                          n, n, dtype, secs * 1e3, tflops)
        info = {
            "device_kind": dev.device_kind,
            "platform": dev.platform,
            "results": entries,
            "computing_power": self.computing_power(entries),
        }
        return info

    @staticmethod
    def computing_power(entries) -> float:
        """Reference rating: 1000 / gemm-time on the largest f32-equivalent
        problem (veles/accelerated_units.py:853-858: 1000/time units)."""
        best = max((e for e in entries), key=lambda e: e["size"] * (
            2 if e["dtype"] == "float32" else 1))
        return 1000.0 / best["seconds"]


def _resolve_peak(override, table_key: str) -> float:
    override = float(override or 0.0)
    if override > 0:
        return override
    peaks = device_peaks()
    return float(peaks[table_key]) if peaks else 0.0


def resolve_peak_tflops() -> float:
    """The peak-flops denominator for MFU (docs/observability.md
    "Goodput & MFU"): the ``root.common.observe.peak_tflops`` override
    when set, else the device's published bf16 peak
    (:data:`DEVICE_PEAKS`); 0.0 off a TPU — "not measured", which every
    MFU consumer reports as 0 rather than inventing a denominator."""
    return _resolve_peak(root.common.observe.get("peak_tflops", 0.0),
                         "tflops_bf16")


def resolve_peak_hbm_gbps() -> float:
    """The HBM-bandwidth denominator for the decode MBU gauge; same
    rule as :func:`resolve_peak_tflops`."""
    return _resolve_peak(root.common.observe.get("peak_hbm_gbps", 0.0),
                         "hbm_gbps")


def mfu_fraction(flops: float, wall_s: float, peak_tflops: float) -> float:
    """Model FLOPs utilization: achieved flops/s over the device's peak.
    0.0 whenever any input is unknown/degenerate — an MFU of 0 reads as
    "not measured", never as a fake 100%."""
    if flops <= 0 or wall_s <= 0 or peak_tflops <= 0:
        return 0.0
    return (flops / wall_s) / (peak_tflops * 1e12)


def epoch_goodput(flops_per_step: float, steps: float, wall_s: float,
                  peak_tflops: Optional[float] = None) -> Dict:
    """Goodput arithmetic for one training epoch, factored pure so the
    MFU math is testable with known flops and a fake clock's wall time:
    achieved flops/s over whatever wall the caller passes, and MFU
    against the device's peak.  The Trainer passes the TRAIN-phase wall
    (loader data waits included; eval and snapshot phases excluded —
    the ``vt_train_phase_seconds`` histogram breaks those out)."""
    if peak_tflops is None:
        peak_tflops = resolve_peak_tflops()
    total = float(flops_per_step) * float(steps)
    fps = total / wall_s if wall_s > 0 and total > 0 else 0.0
    return {
        "flops_per_step": float(flops_per_step),
        "steps": float(steps),
        "wall_s": float(wall_s),
        "flops_per_sec": fps,
        "peak_tflops": float(peak_tflops),
        "mfu": mfu_fraction(total, wall_s, peak_tflops),
    }


def device_info_path(cache_dir: Optional[str] = None) -> str:
    d = cache_dir or root.common.cache_dir
    return os.path.join(d, "device_infos.json")


def load_device_infos(cache_dir: Optional[str] = None) -> Dict:
    path = device_info_path(cache_dir)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def update_device_info(kind: str, mutate, cache_dir: Optional[str] = None
                       ) -> str:
    """Atomic read-modify-write of one device-kind record. Concurrent
    trainers/benchmarks (multi-process GA/ensemble pools, multi-host
    launches) share this DB; an unlocked load→save would clobber entries
    written in between. Writers serialize on a SIDECAR lock file (the DB
    file itself is replaced by rename, so locking its inode would race),
    and the tmp-write + os.replace keeps the DB complete at every instant
    for lock-free readers (load_device_infos)."""
    import fcntl
    path = device_info_path(cache_dir)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            infos = load_device_infos(cache_dir)
        except json.JSONDecodeError:  # pre-rename-era torn file
            infos = {}
        info = infos.get(kind, {"device_kind": kind})
        mutate(info)
        infos[kind] = info
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(infos, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    return path


def save_device_info(info: Dict, cache_dir: Optional[str] = None) -> str:
    """Persist per device kind — the analog of the reference's
    devices/device_infos.json block-size DB. Merges under the DB lock so
    concurrent writers of other keys are not clobbered."""
    return update_device_info(info["device_kind"],
                              lambda rec: rec.update(info), cache_dir)


def benchmark_device(cache_dir: Optional[str] = None, refresh: bool = False,
                     **kw) -> Dict:
    """Cached rating lookup (reference re-measured every 120 s on slaves;
    device kind is stable per process here, so cache on disk keyed by kind
    and refresh on demand)."""
    import jax
    kind = jax.devices()[0].device_kind
    if not refresh:
        cached = load_device_infos(cache_dir).get(kind)
        if cached:
            return cached
    info = DeviceBenchmark(**kw).run()
    save_device_info(info, cache_dir)
    return info
