"""Compiled-artifact runner: serve a sealed StableHLO export with zero
model Python.

``export_compiled()`` (export/compiled.py) seals a workflow's inference
step family — the decode engine's fixed program set plus the batched
forward — into a directory of serialized StableHLO programs, a
manifest, and a weights blob.  :class:`ArtifactRunner` is the other
half: it loads that directory and serves ``generate()``-compatible
decode through the SAME continuous-batching scheduler as the live
:class:`~veles_tpu.runtime.engine.DecodeEngine` (it *is* one — the
subclass only replaces the three program hooks), except that no model
code is ever traced: every program is ``jax.export.deserialize``d and
AOT-compiled at load, and the StepCache counters stay flat from the
first request to the last, across hot swaps included (the
tests/test_artifact.py contract).

Integrity and failure semantics mirror snapshots: every blob's sha256
is verified against the manifest before anything runs
(:class:`~veles_tpu.runtime.snapshotter.SnapshotCorruptError` on
mismatch), a serialized program from a newer ``jax.export`` calling
convention fails with :class:`ArtifactVersionError` naming both
versions (re-export, don't guess), and a foreign platform fails before
the first request rather than mid-decode.

The control plane speaks ``artifact://`` too: ``ModelRegistry`` entries
carry ``kind="artifact"``, ``DeployController.reload`` hot-swaps a live
engine onto an artifact's weights, and ``veles-tpu --serve --artifact
DIR`` boots this runner without the model's Python config at all.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax
import jax.export  # noqa: F401 — not auto-imported by `import jax`
import jax.numpy as jnp
import numpy as np

from ..export.compiled import FORMAT, FORMAT_VERSION, MANIFEST
from .engine import DecodeEngine
from .snapshotter import SnapshotCorruptError, _unflatten, sha256_files
from .step_cache import StepCache


class ArtifactError(RuntimeError):
    """The artifact is structurally unusable here (missing manifest,
    missing program, foreign platform) — distinct from integrity
    corruption (:class:`SnapshotCorruptError`: re-fetch the bytes) and
    from version skew (:class:`ArtifactVersionError`: re-export)."""


class ArtifactVersionError(ArtifactError):
    """The serialized programs use a ``jax.export`` calling convention
    this process cannot replay — the fix is re-exporting the artifact
    with a matching jax, not retrying the load."""


def is_artifact_dir(path: str) -> bool:
    """Directory holds a compiled-artifact manifest — the control
    plane's dispatch test (before the package's contents.json test)."""
    return os.path.isfile(os.path.join(str(path), MANIFEST))


def read_manifest(art_dir: str) -> dict:
    """Parse ``artifact.json`` (no blob verification — that is
    :func:`verify_artifact`'s job, and the runner always runs both)."""
    path = os.path.join(art_dir, MANIFEST)
    try:
        with open(path) as f:
            man = json.load(f)
    except FileNotFoundError:
        raise ArtifactError(
            f"{art_dir!r} is not a compiled artifact (no {MANIFEST}; "
            "produce one with export_compiled / veles-tpu --export "
            "--compiled)") from None
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise SnapshotCorruptError(
            f"{path}: unparseable artifact manifest ({e})") from e
    if not isinstance(man, dict) or man.get("format") != FORMAT:
        raise ArtifactError(f"{path}: not a compiled-artifact manifest")
    try:
        ver = int(man.get("format_version", 1))
    except (TypeError, ValueError) as e:
        raise SnapshotCorruptError(
            f"{path}: artifact manifest is damaged (format_version "
            f"{man.get('format_version')!r}) — re-export") from e
    if int(ver) > FORMAT_VERSION:
        raise ArtifactVersionError(
            f"{path}: artifact format version {ver} is newer than this "
            f"veles-tpu understands ({FORMAT_VERSION}) — upgrade, or "
            "re-export with a matching version")
    # structural keys the consumers index unconditionally: a
    # parseable-but-damaged manifest must raise the corruption error
    # here, not a bare KeyError from the first man["tensors"] /
    # man["slots"] / input_spec["shape"]
    progs = man.get("programs") or {}
    entries = []
    ok = isinstance(man.get("tensors"), str) and isinstance(progs, dict)
    if ok:
        for key, p in progs.items():
            entries.extend(p.values() if key == "prefill"
                           and isinstance(p, dict) else [p])
        ok = all(isinstance(q, dict) and isinstance(q.get("file"), str)
                 for q in entries)
    if ok and isinstance(progs.get("prefill"), dict):
        # bucket keys index the program inventory as ints
        ok = all(str(k).isdigit() for k in progs["prefill"])
    if ok and "decode" in progs:  # the ArtifactRunner geometry keys
        ok = all(isinstance(man.get(k), int)
                 for k in ("slots", "l_max", "bucket_min"))
        if ok and man.get("paged"):  # v2 paged layout: pool geometry
            ok = all(isinstance(man.get(k), int)
                     for k in ("page_size", "pages"))
    if ok and "forward" in progs:  # load_forward's input signature
        ispec = man.get("input_spec")
        ok = isinstance(ispec, dict) and isinstance(
            ispec.get("shape"), list) and "dtype" in ispec
    if not ok:
        raise SnapshotCorruptError(
            f"{path}: artifact manifest is damaged (tensors, program "
            "file, geometry, or input_spec entries missing or "
            "malformed) — re-export")
    return man


def _verify_blob(path: str, want: Optional[str]) -> None:
    """One blob against its manifest sha256 (no-op without one) —
    SnapshotCorruptError on unreadable or mismatching bytes."""
    if not want:
        return
    try:
        got = sha256_files([path])
    except OSError as e:
        raise SnapshotCorruptError(
            f"{path}: artifact blob unreadable ({e})") from e
    if got != want:
        raise SnapshotCorruptError(
            f"{path}: artifact checksum mismatch (manifest "
            f"{want[:12]}…, blob {got[:12]}…)")


def verify_artifact(art_dir: str, man: dict) -> None:
    """Check every blob the manifest names against its recorded sha256
    — the snapshot checksum discipline applied to the artifact: torn
    or bit-flipped bytes raise :class:`SnapshotCorruptError` BEFORE a
    single program deserializes."""
    blobs = [(man["tensors"], man.get("tensors_sha256"))]
    progs = man.get("programs", {})
    for key, p in progs.items():
        if key == "prefill":
            blobs.extend((q["file"], q.get("sha256"))
                         for q in p.values())
        else:
            blobs.append((p["file"], p.get("sha256")))
    for rel, want in blobs:
        _verify_blob(os.path.join(art_dir, rel), want)


def load_artifact_weights(art_dir: str, man: Optional[dict] = None,
                          *, verify: bool = True) -> Dict[str, dict]:
    """The weights blob as host numpy trees ``{"params": ..,
    "state": ..}`` — what the deploy control plane hot-swaps onto a
    LIVE engine from an ``artifact://`` source (the programs stay the
    live engine's own; same-architecture weights are all a swap moves).
    """
    man = man if man is not None else read_manifest(art_dir)
    npz_path = os.path.join(art_dir, man["tensors"])
    if verify:
        _verify_blob(npz_path, man.get("tensors_sha256"))
    try:
        with np.load(npz_path, allow_pickle=False) as z:
            flat = {k: z[k] for k in z.files}
    except (OSError, ValueError, EOFError) as e:
        raise SnapshotCorruptError(
            f"{npz_path}: artifact tensors unreadable ({e})") from e
    tree = _unflatten(flat)
    return {"params": tree.get("params") or {},
            "state": tree.get("state") or {}}


def _check_platform(info: dict, what: str) -> None:
    """The serving platform must be one the program was lowered for —
    fail at LOAD, not mid-request (the documented semantics)."""
    platform = jax.default_backend()
    declared = info.get("platforms") or []
    # default_backend says 'gpu' where jax.export canonicalizes the
    # lowering platform to 'cuda'/'rocm' — compare the whole alias set,
    # or every GPU-exported artifact would be refused on GPU
    aliases = {platform} | ({"cuda", "rocm"} if platform == "gpu"
                            else set())
    if declared and not aliases & set(declared):
        raise ArtifactError(
            f"artifact program {what!r} was exported for platform(s) "
            f"{declared}, this process runs {platform!r} — re-export "
            "on (or for) the serving platform")


def _check_version(man: dict, what: str, info: dict) -> None:
    ver = info.get("calling_convention_version")
    if ver is None:
        return
    lo = jax.export.minimum_supported_calling_convention_version
    hi = jax.export.maximum_supported_calling_convention_version
    if not lo <= int(ver) <= hi:
        raise ArtifactVersionError(
            f"artifact program {what!r} was serialized with jax.export "
            f"calling convention {ver} (exporter jax "
            f"{man.get('jax_version')}), but this jax {jax.__version__} "
            f"supports [{lo}, {hi}] — re-export the artifact with a "
            "matching jax version")


def _deserialize(art_dir: str, man: dict, what: str, info: dict):
    _check_platform(info, what)
    _check_version(man, what, info)
    path = os.path.join(art_dir, info["file"])
    with open(path, "rb") as f:
        data = f.read()
    try:
        return jax.export.deserialize(bytearray(data))
    except Exception as e:  # noqa: BLE001 — flatbuffer/version errors
        # checksums already passed, so bad bytes mean producer/consumer
        # skew, not corruption in transit
        raise ArtifactVersionError(
            f"artifact program {what!r} failed to deserialize "
            f"({type(e).__name__}: {e}); it was exported by jax "
            f"{man.get('jax_version')} — re-export with a jax this "
            f"process ({jax.__version__}) can replay") from e


def _zeros_from_rows(rows) -> dict:
    """Rebuild a zeroed pytree from manifest ``[{path, shape, dtype}]``
    rows (the cache skeleton — the runner owns the slot state without
    ever seeing the model's cache-construction code).  Structural
    marker rows (``__seq__`` / ``__emptydict__``) replay their recorded
    values — _unflatten reads them to rebuild sequences and empty
    dicts."""
    flat = {}
    for r in rows:
        if "structure" in r:
            flat[r["path"]] = np.asarray(r["structure"],
                                         np.dtype(r["dtype"]))
        else:
            flat[r["path"]] = jnp.zeros(tuple(r["shape"]),
                                        jnp.dtype(r["dtype"]))
    if set(flat) <= {"/__emptydict__"}:
        return {}  # cache-free chain: _unflatten can't see a top-level
    return _unflatten(flat)  # empty dict behind the marker's prefix


def load_forward(art_dir: str):
    """Load ONLY the batched forward program of an artifact (the leg
    every export carries, decodable chain or not): returns
    ``(predict_fn, wstate, manifest)`` where ``predict_fn(wstate,
    batch)`` follows the ``make_predict_step`` contract — what
    ``--serve --artifact`` boots for a forward-only model."""
    art_dir = str(art_dir)
    man = read_manifest(art_dir)
    verify_artifact(art_dir, man)
    progs = man.get("programs", {})
    if "forward" not in progs:
        raise ArtifactError(
            f"artifact {art_dir!r} holds no forward program (exported "
            "without an input spec?)")
    exp = _deserialize(art_dir, man, "forward", progs["forward"])
    parts = load_artifact_weights(art_dir, man, verify=False)
    wstate = {"params": jax.device_put(parts["params"]),
              "state": jax.device_put(parts["state"])}
    # AOT-compile NOW (jax.jit alone is lazy): a program this process
    # can't lower must fail here, not inside the first /predict
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), t)
    ispec = man.get("input_spec") or {}
    fn = jax.jit(exp.call).lower(
        sds(wstate["params"]), sds(wstate["state"]),
        jax.ShapeDtypeStruct(tuple(int(s) for s in ispec["shape"]),
                             jnp.dtype(ispec["dtype"]))).compile()

    def predict(wstate, batch):
        return fn(wstate["params"], wstate.get("state") or {},
                  batch["@input"])

    return predict, wstate, man


class ArtifactRunner(DecodeEngine):
    """A :class:`DecodeEngine` whose programs come from a sealed
    artifact instead of traced model code.

    Same public contract — ``submit`` / ``generate`` / ``swap_params``
    / ``drain`` / ``stats`` and the REST + deploy integrations — with
    the three program hooks replaced: caches rebuild from manifest
    avals, the head width is the manifest's ``vocab``, and
    prefill/decode are ``jax.export.deserialize``d programs AOT-compiled
    at load through the StepCache (every compile happens HERE; the
    counters must not move afterwards — per request, per swap).
    """

    def __init__(self, art_dir: str, *,
                 window_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 deadline_s: Optional[float] = None, status=None,
                 spec: Optional[bool] = None,
                 megastep: Optional[int] = None):
        self.art_dir = str(art_dir)
        man = read_manifest(self.art_dir)
        verify_artifact(self.art_dir, man)
        progs = man.get("programs", {})
        if "decode" not in progs:
            why = man.get("decode_unsupported", "forward-only export")
            raise ArtifactError(
                f"artifact {art_dir!r} holds no decode program ({why}); "
                "ArtifactRunner serves decode — a forward-only "
                "artifact loads through load_forward() instead")
        # speculative decode is served iff the verify program is part
        # of the SEALED inventory (manifest spec_decode + the program
        # blob).  Default: serve what the artifact seals; an explicit
        # spec=True against an unsealed artifact is refused loudly —
        # the runner has no model code to trace a verify program from.
        spec_meta = man.get("spec_decode") or None
        if spec_meta is not None and (
                not isinstance(spec_meta, dict)
                or not isinstance(spec_meta.get("k"), int)
                or "verify" not in progs):
            raise SnapshotCorruptError(
                f"{art_dir}: artifact manifest spec_decode entry is "
                "damaged (no static k, or no sealed verify program) — "
                "re-export")
        want_spec = bool(spec_meta) if spec is None else bool(spec)
        if want_spec and spec_meta is None:
            raise ArtifactError(
                f"artifact {art_dir!r} seals no speculative verify "
                "program (spec_decode absent from the manifest); "
                "re-export with export_compiled(..., spec=True) — the "
                "runner cannot trace one from sealed programs")
        # megastep decode is served iff the fused program is part of
        # the SEALED inventory (manifest megastep + the program blob);
        # artifacts without it — every v1/v2, and v3 exports at
        # megastep=1 — load unchanged and serve plain per-token decode.
        # An explicit megastep > 1 must match the sealed static N: the
        # runner has no model code to trace another fused program from.
        mega_meta = man.get("megastep") or None
        if mega_meta is not None and (
                not isinstance(mega_meta, dict)
                or not isinstance(mega_meta.get("n"), int)
                or mega_meta["n"] < 2
                or "megastep" not in progs):
            raise SnapshotCorruptError(
                f"{art_dir}: artifact manifest megastep entry is "
                "damaged (no static n >= 2, or no sealed megastep "
                "program) — re-export")
        sealed_n = int(mega_meta["n"]) if mega_meta else 1
        want_mega = sealed_n if megastep is None else int(megastep)
        if want_mega > 1 and want_mega != sealed_n:
            raise ArtifactError(
                f"artifact {art_dir!r} seals "
                + (f"megastep N={sealed_n}" if sealed_n > 1
                   else "no megastep program")
                + f", megastep={want_mega} was requested; re-export "
                "with export_compiled(..., megastep=N) — the runner "
                "cannot trace one from sealed programs")

        self.manifest = man
        self.workflow = None            # the whole point: no model code
        self.workflow_checksum = man.get("workflow_checksum")
        # embedding rows (None without an Embedding front) — the INPUT
        # token bound, distinct from the output head width self._vocab
        self.input_vocab = man.get("input_vocab")
        self.plan = None
        self._ctx = None
        self.cache_dtype = jnp.dtype(man.get("cache_dtype", "float32"))
        # sealed geometry: slots/l_max/bucket_min — and for v2 paged
        # artifacts the page-pool shape — come from the manifest (the
        # bucket table AND the page-table calling convention are the
        # program inventory, not a config preference).  prefix_reuse is
        # the exporter's record of whether the chain's cached state is
        # pure attention KV; the scheduler-side prefix cache keys off it
        # because the runner has no DecodePlan to inspect.
        self._prefix_ok = bool(man.get("prefix_reuse", False))
        self._init_config(slots=man["slots"], l_max=man["l_max"],
                          window_ms=window_ms, queue_depth=queue_depth,
                          deadline_s=deadline_s,
                          bucket_min=man["bucket_min"],
                          paged=bool(man.get("paged", False)),
                          page_size=man.get("page_size"),
                          pages=man.get("pages"),
                          paged_kernel=bool(man.get("paged_kernel",
                                                    False)),
                          spec=want_spec,
                          spec_k=(int(spec_meta["k"]) if want_spec
                                  else None),
                          megastep=want_mega)
        # v3 calling convention (manifest ``prefill_start``): the sealed
        # prefill programs take the traced ``start``, so chunked prefill
        # and preempt-resume are plain bucket calls on them.  Absent
        # (v1/v2 exports), the dense programs keep the whole-prompt
        # convention and chunking is gated off — an old PAGED program
        # does take ``start``, but its body resets recurrent carry at
        # every call, so mid-prompt continuation is only trusted when
        # the exporter declared it (docs/serving.md "Overload
        # survival").  Overrides the live-builder defaults
        # _init_config just set.
        self._prefill_start = bool(man.get("prefill_start", False))
        self._chunk_capable = self._prefill_start
        self.step_cache = StepCache()
        self.status = status

        self._exp_decode = _deserialize(self.art_dir, man, "decode",
                                        progs["decode"])
        # deserialized BEFORE _init_runtime: the base engine compiles
        # the verify program there when spec is on
        self._exp_verify = (
            _deserialize(self.art_dir, man, "verify", progs["verify"])
            if want_spec else None)
        # same load-before-_init_runtime ordering: the base engine
        # compiles the megastep program there when megastep > 1
        self._exp_mega = (
            _deserialize(self.art_dir, man, "megastep",
                         progs["megastep"])
            if want_mega > 1 else None)
        self._exp_prefill = {
            int(pb): _deserialize(self.art_dir, man, f"prefill_{pb}", q)
            for pb, q in progs.get("prefill", {}).items()}
        self._exp_forward = (
            _deserialize(self.art_dir, man, "forward", progs["forward"])
            if "forward" in progs else None)

        parts = load_artifact_weights(self.art_dir, man, verify=False)
        self.wstate = {"params": jax.device_put(parts["params"]),
                       "state": jax.device_put(parts["state"])}
        self._init_runtime(self.wstate["params"])
        # prefill programs are deserialized already; compile them ALL at
        # boot so the counters never move once traffic flows (the live
        # engine compiles buckets lazily; a sealed artifact knows its
        # whole inventory up front)
        for pb in sorted(self._exp_prefill):
            self._prefill_fn(pb, self.wstate["params"])
        self._forward = None
        if self._exp_forward is not None:
            args = (self._sds(self.wstate["params"]),
                    self._sds(self.wstate["state"]),
                    jax.ShapeDtypeStruct(
                        tuple(man["input_spec"]["shape"]),
                        jnp.dtype(man["input_spec"]["dtype"])))
            self._forward, _, _ = self.step_cache.get_step(
                "forward", (man["input_spec"]["shape"][0],),
                lambda: (jax.jit(self._exp_forward.call), None, None),
                args)
        self.info(
            "artifact %s: %d programs (%d prefill buckets%s%s%s), "
            "vocab=%s, %d compiles at load",
            self.art_dir, len(self._exp_prefill) + 1
            + (self._exp_forward is not None)
            + (self._exp_verify is not None)
            + (self._exp_mega is not None),
            len(self._exp_prefill),
            ", forward" if self._exp_forward is not None else "",
            f", verify k={self.spec_k}" if self._exp_verify is not None
            else "",
            f", megastep n={self.megastep}"
            if self._exp_mega is not None else "",
            man.get("vocab"), self.step_cache.compiles)

    # -- program hooks (everything else is the engine, unchanged) -----------
    def _make_caches(self, params):
        return _zeros_from_rows(self.manifest.get("caches", []))

    def _head_width(self, params) -> int:
        vocab = self.manifest.get("vocab")
        if vocab is None:
            raise ArtifactError(
                "artifact manifest records no vocab — it predates the "
                "decode leg; re-export with export_compiled")
        return int(vocab)

    def _compile_decode(self, params):
        step, _, _ = self.step_cache.get_step(
            "decode", self._geometry_key(),
            lambda: (jax.jit(self._exp_decode.call,
                             donate_argnums=(1, 2)), None, None),
            self._decode_args_sds(params), pin=(self._exp_decode,))
        return step

    def _compile_verify(self, params):
        step, _, _ = self.step_cache.get_step(
            "verify", self._geometry_key() + ("k", self.spec_k),
            lambda: (jax.jit(self._exp_verify.call,
                             donate_argnums=(1, 2)), None, None),
            self._verify_args_sds(params), pin=(self._exp_verify,))
        return step

    def _compile_megastep(self, params):
        step, _, _ = self.step_cache.get_step(
            "megastep", self._geometry_key() + ("mega", self.megastep),
            lambda: (jax.jit(self._exp_mega.call,
                             donate_argnums=(1, 2)), None, None),
            self._decode_args_sds(params), pin=(self._exp_mega,))
        return step

    def _prefill_fn(self, pb: int, params, full_ctx: bool = True):
        # ``full_ctx`` is a live-builder compile choice; a sealed
        # inventory has exactly one program per bucket (v3 seals the
        # chunk-capable full-context form, v1/v2 their whole-prompt
        # convention), so the hint is accepted and ignored
        exp = self._exp_prefill.get(int(pb))
        if exp is None:
            raise ArtifactError(
                f"artifact has no prefill program for bucket {pb} "
                f"(inventory: {sorted(self._exp_prefill)}) — the "
                "manifest's bucket table is the sealed program set")
        step, _, _ = self.step_cache.get_step(
            "prefill", (pb,) + self._geometry_key(),
            lambda: (jax.jit(exp.call, donate_argnums=(1, 2)),
                     None, None),
            self._prefill_args_sds(params, pb), pin=(exp,))
        return step

    # -- forward leg ---------------------------------------------------------
    @property
    def has_forward(self) -> bool:
        return self._forward is not None

    def predict(self, wstate, batch):
        """``make_predict_step`` contract over the exported forward
        program — drop-in for RestfulServer's ``predict_fn`` (the
        wstate argument keeps hot swaps visible: the server passes its
        own live reference, which the deploy flip replaces)."""
        if self._forward is None:
            raise ArtifactError(
                "artifact was exported without a forward program")
        return self._forward(wstate["params"], wstate.get("state") or {},
                             batch["@input"])

    def stats(self) -> dict:
        st = super().stats()
        st["artifact"] = {
            "dir": self.art_dir,
            "workflow": self.manifest.get("workflow"),
            "checksum": (self.workflow_checksum or "")[:12],
            "jax_version": self.manifest.get("jax_version"),
            "programs": len(self._exp_prefill) + 1
            + (self._exp_forward is not None)
            + (self._exp_verify is not None)
            + (self._exp_mega is not None),
        }
        return st
