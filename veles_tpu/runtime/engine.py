"""Continuous-batching decode engine: slot-based serving with bucketed
prefill and a lifetime-compiled decode step.

``generate()`` is the wrong engine for serving: every distinct
``(B, P, n_steps, sampling)`` tuple compiles a fresh whole-sequence scan
and requests run serially — the opposite of the ROADMAP's "heavy traffic"
north star, and the reason the reference project shipped a standalone
inference runtime (libVeles) instead of serving from its training graph.
This module applies the fixed-shape AOT discipline TPUs impose (PAPERS:
"Automatic Full Compilation ... to Cloud TPUs") to decode, the way PR 1's
StepCache applied it to training:

* the engine owns a fixed-capacity **slot batch** ``(slots, l_max)`` of
  KV caches (plus recurrent carried state) for its whole lifetime;
* it compiles exactly **two kinds of programs**, AOT via the same
  :class:`~veles_tpu.runtime.step_cache.StepCache` whose counters tests
  assert on: a *bucketed prefill* (prompt lengths padded to power-of-two
  buckets, so at most ``log2(l_max)``-ish compiles ever) and a single
  *decode step* advancing every active slot one token with per-slot
  positions, per-slot sampling params, and per-slot eos / length
  retirement — total programs ≤ bucket count + 1, recompiles 0;
* a host-side scheduler thread owns the request queue: admission into
  free slots happens **mid-flight** (no drain barrier — running slots
  keep decoding across an admission), finished sequences retire and free
  their slot immediately, a small batching window coalesces concurrent
  arrivals, a bounded queue raises :class:`EngineOverloaded` (HTTP 429 +
  Retry-After in restful.py) instead of unbounded latency, and per-
  request deadlines fail requests loudly instead of wedging a slot.

Result parity: greedy tokens are identical to per-request ``generate()``
calls (the step math IS ``DecodePlan.step``, just masked/batched), and
sampled tokens are bitwise-identical for single-row requests with the
same key — per-slot keys fold in the slot's own position exactly like
the ``generate()`` scan (multi-row sampled requests draw per-row keys
``fold_in(key, row)`` instead of one batched categorical, documented in
docs/serving.md).

**Paged KV cache + shared-prefix reuse** (default; disable with
``root.common.serve.paged = False``): instead of one dense ``(slots,
l_max)`` KV row per slot — which caps concurrency by HBM at
``slots * l_max`` token-cells even though most requests use a fraction
of ``l_max`` — the engine owns a fixed pool of ``root.common.serve
.pages`` pages of ``page_size`` tokens each, and every slot maps its
logical positions onto pool pages through an int32 page table threaded
through the SAME two program kinds as traced data flow (gather/scatter
on the page axis — no third program, StepCache counters stay flat
across page allocation, reclamation, prefix hits, and copy-on-write).
The host scheduler refcounts pages and keeps a chained content-hash
index over full prompt pages: a request whose prompt prefix matches a
cached page chain maps those pages read-only (refcount++) and prefills
only its tail — N requests sharing a system prompt prefill it ONCE —
with copy-on-write semantics at the first divergent token (the
divergent page is recomputed into a private page; shared pages are
never written: decode/prefill writes of masked-off rows route to a
scratch pool row).  A request that cannot get pages is refused with
the same 429/Retry-After backpressure as a full queue
(docs/serving.md "Paged KV cache").
"""

from __future__ import annotations

import collections
import hashlib
import json
import math
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import root
from ..logger import Logger
from ..units.base import Context
from .admission import AdmissionController
from .benchmark import resolve_peak_hbm_gbps
from .generate import DecodePlan
from .memory import memory_monitor, tree_bytes
from .metrics import ScopedCounter, next_trace_id, registry, span_ring
from .slo import slo_tracker
from .step_cache import (StepCache, enable_persistent_cache,
                         tree_signature)


class EngineOverloaded(RuntimeError):
    """Request queue is full; retry after ``retry_after_s`` seconds.

    Interactive overload hints come from :meth:`_retry_after` (floored
    at 1s — real congestion drains slowly); the batch trough-closed 429
    passes a sub-second hint instead, because trough state flips at
    slot granularity and a 1s floor would make the job manager sleep
    through every short trough it exists to harvest."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = max(0.0, float(retry_after_s))


class EngineStopped(RuntimeError):
    """The engine was stopped before this request completed."""


class SchedulerCrashed(RuntimeError):
    """The scheduler loop died with an unhandled exception: every queued
    and mid-flight request was failed with this error, and new submits
    keep raising it.  Deliberately NOT an :class:`EngineStopped` — a
    crash is a 500 (page someone), not a 503 drain a load balancer
    routes around (runtime/restful.py)."""


class EngineDraining(EngineStopped):
    """The engine is draining: in-flight work retires, new work is
    refused (the REST layer's 503 on ``/ready`` and ``/generate``)."""


def prefix_page_hashes(prompt, page_size: int) -> list:
    """Chained sha256 digests of a prompt's FULL ``page_size``-token
    pages: page ``i``'s key covers tokens ``0 .. (i+1)*page_size`` —
    KV content depends on the whole prefix, not just the page's own
    tokens.  This is THE prefix-cache identity (docs/serving.md "Paged
    KV cache"): the engine keys its refcounted prefix index on it, and
    the fleet router (runtime/fleet.py) computes the SAME digests over
    a prompt head to route same-system-prompt sessions to the replica
    already holding those pages — one function so the two can never
    drift.  ``prompt`` is any 1-D int array-like; hashes are over the
    int32 byte view, matching what the engine stores."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    psz = int(page_size)
    hashes, h = [], b""
    for i in range(int(prompt.size) // psz):
        h = hashlib.sha256(
            h + prompt[i * psz:(i + 1) * psz].tobytes()).digest()
        hashes.append(h)
    return hashes


# KV-page transfer wire magic (docs/serving.md "Disaggregated
# prefill/decode"): version byte baked into the tag so a future format
# bump rejects loudly instead of misparsing
_KV_MAGIC = b"VTKV1\x00"


def signature_mismatch(expected, got, limit: int = 6) -> str:
    """Human-readable diff of two :func:`tree_signature` results — the
    clear-error half of the hot-swap contract: name WHICH leaves differ
    instead of dumping two thousand-entry tuples at the operator."""
    exp = {p: (s, d) for p, s, d in expected}
    new = {p: (s, d) for p, s, d in got}

    def fmt(sd):  # dtype may be blank (shape-only signatures)
        return f"{sd[0]}/{sd[1]}" if sd[1] else f"{sd[0]}"

    msgs = []
    for p in sorted(set(exp) - set(new)):
        msgs.append(f"{p}: missing (expected {fmt(exp[p])})")
    for p in sorted(set(new) - set(exp)):
        msgs.append(f"{p}: unexpected leaf {fmt(new[p])}")
    for p in sorted(set(exp) & set(new)):
        if exp[p] != new[p]:
            msgs.append(f"{p}: {fmt(new[p])} != expected {fmt(exp[p])}")
    extra = len(msgs) - limit
    if extra > 0:
        msgs = msgs[:limit] + [f"... and {extra} more"]
    return "; ".join(msgs) or "identical signatures"


def place_like(tree, template):
    """Device-place ``tree`` mirroring ``template``'s shardings (a bare
    device_put would commit a sharded model's replacement to one device
    — recompile or OOM on the next step), blocking until every leaf is
    fully transferred.  Placement errors propagate: committing the tree
    to the wrong devices as a "fallback" would be strictly worse than
    failing the swap with the old version still serving.  Host-array
    templates (no ``.sharding``) take default placement."""
    try:
        shardings = jax.tree.map(lambda l: l.sharding, template)
    except AttributeError:  # host/numpy template leaves
        shardings = None
    placed = jax.device_put(tree, shardings) if shardings is not None \
        else jax.device_put(tree)
    for leaf in jax.tree.leaves(placed):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return placed


def make_decode_fn(plan, ctx, S: int, *, page_size: Optional[int] = None,
                   paged_kernel: bool = False):
    """The engine's lifetime decode program as an un-compiled jitted
    function: advance all S slots one token with per-slot positions,
    sampling params, and eos/length retirement.  Lives at module level
    (not closed inside the engine) so the compiled-artifact exporter
    (export/compiled.py) serializes EXACTLY the program the live engine
    runs — a single source of step math, never two.

    With ``page_size`` set the signature gains the per-slot page table
    ``ptab`` (S, n_ptab) int32 and the KV caches are the flat page pool
    (page indirection is traced data flow through the same program
    kind); inactive slots' KV writes route to the scratch pool row so a
    retired slot can never corrupt pages reassigned to another slot.
    On BOTH layouts the ``active`` mask also drops inactive rows' dense
    KV scatters and freezes their recurrent carry (``write_ok`` /
    ``carry_ok`` in plan.step): an inactive slot may be mid-CHUNKED-
    prefill, its rows being filled slice by slice, and a stale-position
    write or a carry advance between slices would corrupt the very
    state the next slice continues from (docs/serving.md "Overload
    survival").  ``paged_kernel`` routes the paged attention read
    through the fused Pallas kernel (bounded-error;
    runtime/generate.py)."""

    def step_tail(caches, toks, logits, pos, active, temp, topk, topp,
                  eos, end, keys, rows):
        step_keys = jax.vmap(jax.random.fold_in)(
            jax.random.wrap_key_data(keys), pos)
        nxt = _sample_slots(logits, step_keys, temp, topk, topp)
        new_pos = jnp.where(active, pos + 1, pos)
        cur = toks[rows, new_pos]
        toks = toks.at[rows, new_pos].set(jnp.where(active, nxt, cur))
        finished = active & ((nxt == eos) | (new_pos >= end))
        return caches, toks, new_pos, active & ~finished, finished

    if page_size is None:
        def decode_step(params, caches, toks, pos, active, temp, topk,
                        topp, eos, end, keys):
            rows = jnp.arange(S)
            tok = toks[rows, pos]
            logits, caches = plan.step(params, caches, tok, pos, ctx,
                                       write_ok=active)
            return step_tail(caches, toks, logits, pos, active, temp,
                             topk, topp, eos, end, keys, rows)
    else:
        def decode_step(params, caches, toks, ptab, pos, active, temp,
                        topk, topp, eos, end, keys):
            rows = jnp.arange(S)
            tok = toks[rows, pos]
            logits, caches = plan.step(
                params, caches, tok, pos, ctx,
                pages=(ptab, page_size, active),
                paged_kernel=paged_kernel)
            return step_tail(caches, toks, logits, pos, active, temp,
                             topk, topp, eos, end, keys, rows)

    return jax.jit(decode_step, donate_argnums=(1, 2))


def make_verify_fn(plan, ctx, S: int, K: int, *,
                   page_size: Optional[int] = None,
                   paged_kernel: bool = False):
    """The engine's speculative **verify** program — the third (and
    last) program kind next to prefill and decode, compiled once per
    engine lifetime for a STATIC draft length ``K`` (module-level for
    the same exporter single-source reason as :func:`make_decode_fn`).

    ``draft`` (S, K) int32 carries each slot's host-drafted candidate
    tokens (``-1`` entries never match — the no-draft fallback row).
    One call scores all ``K + 1`` positions in one target forward (an
    in-program scan of the SAME ``DecodePlan.step`` the decode program
    runs — the idiom prefill already uses) and, per slot, accepts the
    longest draft prefix whose tokens equal what the engine's own
    sampler would have chosen at each position, then emits the first
    non-matching (bonus) token.  Because the sampler's choice at a
    position is a deterministic function of (logits, per-slot key
    folded at that GLOBAL position), the emitted sequence is
    **bitwise** the non-speculative engine's for greedy AND sampled
    slots — the drafter only guesses which tokens the sampler will
    pick, it never changes the pick (docs/serving.md "Speculative
    decoding").

    Per micro-step, a slot still extending feeds its last written token
    at its own position (KV write included — identical to a decode
    step), samples the next token, writes it, and keeps extending only
    while the draft matched and neither eos nor the length bound hit
    (mid-block eos retirement: later micro-steps leave the slot
    untouched).  Slots not extending re-feed their last token with KV
    writes routed to the scratch pool row (paged) or dropped (dense)
    and their recurrent carry frozen — state provably unchanged (the
    same ``write_ok`` discipline as :func:`make_decode_fn`; a cell
    iteration is not idempotent, and a mid-chunk slot's rows must not
    be touched between its slices).  Returns
    ``(caches, toks, pos, active, finished, accepted)`` where
    ``accepted`` (S,) int32 counts draft tokens whose emission matched
    the proposal (the accept-rate numerator)."""

    def verify_core(params, caches, toks, ptab, pos, active, temp,
                    topk, topp, eos, end, keys, draft):
        rows = jnp.arange(S)

        def body(carry, i):
            caches, toks, p, alive, fin, acc = carry
            tok = toks[rows, p]
            if page_size is None:
                logits, caches2 = plan.step(params, caches, tok, p, ctx,
                                            write_ok=alive)
            else:
                logits, caches2 = plan.step(
                    params, caches, tok, p, ctx,
                    pages=(ptab, page_size, alive),
                    paged_kernel=paged_kernel)
            step_keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(keys), p)
            nxt = _sample_slots(logits, step_keys, temp, topk, topp)
            new_p = jnp.where(alive, p + 1, p)
            cur = toks[rows, new_p]
            toks = toks.at[rows, new_p].set(jnp.where(alive, nxt, cur))
            done = alive & ((nxt == eos) | (new_p >= end))
            # did the emitted token match this micro-step's proposal?
            # (the last micro-step has none: i == K is the bonus slot)
            d_i = draft[rows, jnp.minimum(i, K - 1)]
            match = alive & (i < K) & (nxt == d_i)
            acc = acc + match.astype(jnp.int32)
            fin = fin | done
            alive = alive & match & ~done
            return (caches2, toks, new_p, alive, fin, acc), None

        init = (caches, toks, pos, active, jnp.zeros(S, bool),
                jnp.zeros(S, jnp.int32))
        (caches, toks, pos, _, fin, acc), _ = jax.lax.scan(
            body, init, jnp.arange(K + 1))
        return caches, toks, pos, active & ~fin, fin, acc

    if page_size is None:
        def verify_step(params, caches, toks, pos, active, temp, topk,
                        topp, eos, end, keys, draft):
            return verify_core(params, caches, toks, None, pos, active,
                               temp, topk, topp, eos, end, keys, draft)
    else:
        def verify_step(params, caches, toks, ptab, pos, active, temp,
                        topk, topp, eos, end, keys, draft):
            return verify_core(params, caches, toks, ptab, pos, active,
                               temp, topk, topp, eos, end, keys, draft)

    return jax.jit(verify_step, donate_argnums=(1, 2))


def make_megastep_fn(plan, ctx, S: int, N: int, *,
                     page_size: Optional[int] = None,
                     paged_kernel: bool = False):
    """The engine's decode **megastep** — the fourth program kind: ``N``
    decode micro-steps fused into ONE compiled dispatch (static ``N =
    root.common.serve.megastep``; module-level for the same exporter
    single-source reason as :func:`make_decode_fn`).  The host loop, not
    the math, bounds tokens/s at production batch sizes; keeping the
    token loop inside XLA amortizes the dispatch + scheduler pass to
    once per ``N`` tokens (docs/serving.md "Megastep decode").

    The body is the verify scan (:func:`make_verify_fn`) minus draft
    matching: each micro-step feeds every live slot its last written
    token at its own position, samples with the slot's key folded at
    that GLOBAL position (so emitted tokens are **bitwise** what N
    separate decode steps emit, greedy and sampled alike), writes the
    token, and retires the slot in-program on eos or its length bound.
    A slot retired at micro-step ``i`` stops writing KV, advancing
    recurrent carry, and emitting tokens for steps ``i+1..N`` — the
    ``write_ok`` discipline of :func:`make_decode_fn`, with paged
    masked writes routed to the scratch pool row and dense ones
    dropped, so a retired slot's rows (possibly mid-chunked-prefill
    after reassignment) are provably untouched.

    Same calling convention as the decode program (paged inserts
    ``ptab``).  Returns ``(caches, toks, pos, active, finished,
    emitted)``: ``toks`` holds each slot's emitted-token buffer at
    ``[old_pos+1 .. old_pos+emitted]`` and ``emitted`` (S,) int32
    counts tokens this call emitted per slot — the host retires,
    streams, and accounts them in one bulk pass."""

    def mega_core(params, caches, toks, ptab, pos, active, temp,
                  topk, topp, eos, end, keys):
        rows = jnp.arange(S)

        def body(carry, _):
            caches, toks, p, alive, fin, emitted = carry
            tok = toks[rows, p]
            if page_size is None:
                logits, caches2 = plan.step(params, caches, tok, p, ctx,
                                            write_ok=alive)
            else:
                logits, caches2 = plan.step(
                    params, caches, tok, p, ctx,
                    pages=(ptab, page_size, alive),
                    paged_kernel=paged_kernel)
            step_keys = jax.vmap(jax.random.fold_in)(
                jax.random.wrap_key_data(keys), p)
            nxt = _sample_slots(logits, step_keys, temp, topk, topp)
            new_p = jnp.where(alive, p + 1, p)
            cur = toks[rows, new_p]
            toks = toks.at[rows, new_p].set(jnp.where(alive, nxt, cur))
            emitted = emitted + alive.astype(jnp.int32)
            done = alive & ((nxt == eos) | (new_p >= end))
            fin = fin | done
            alive = alive & ~done
            return (caches2, toks, new_p, alive, fin, emitted), None

        init = (caches, toks, pos, active, jnp.zeros(S, bool),
                jnp.zeros(S, jnp.int32))
        (caches, toks, pos, _, fin, emitted), _ = jax.lax.scan(
            body, init, None, length=N)
        return caches, toks, pos, active & ~fin, fin, emitted

    if page_size is None:
        def megastep(params, caches, toks, pos, active, temp, topk,
                     topp, eos, end, keys):
            return mega_core(params, caches, toks, None, pos, active,
                             temp, topk, topp, eos, end, keys)
    else:
        def megastep(params, caches, toks, ptab, pos, active, temp,
                     topk, topp, eos, end, keys):
            return mega_core(params, caches, toks, ptab, pos, active,
                             temp, topk, topp, eos, end, keys)

    return jax.jit(megastep, donate_argnums=(1, 2))


#: parked/cold speculative-drafting probe interval (scheduler ticks):
#: a workload the drafter cannot pay for decays to plain decode plus
#: one drafting attempt — and, when a draft exists, one measuring
#: verify step — every this many ticks, bounding the overhead of an
#: unpredictable workload to ~(cost ratio - 1)/64 per tick while still
#: re-qualifying speculation within one interval of a workload shift.
_SPEC_PROBE_TICKS = 64


def ngram_draft(hist, k: int, *, n_max: int = 3, n_min: int = 1):
    """Prompt-lookup/n-gram drafter (host-side): propose the ``k``
    tokens that followed the most recent earlier occurrence of the
    history's trailing n-gram, longest match first.  Returns a (k,)
    int32 row padded with ``-1`` past the available continuation, or
    None when no n-gram of any tried length recurs — the draft is a
    guess the verify program checks against the model's own choices,
    so a bad one costs wasted micro-steps, never wrong tokens.  This is
    the second-model-free drafter (``root.common.serve.spec.drafter =
    "ngram"``): repetitive and structured continuations — chat turns
    over a shared system prompt, code, the cycles greedy decode settles
    into — are exactly where trailing n-grams recur.

    The search is ``bytes.rfind`` over the raw int32 buffer (C speed —
    this runs per slot per scheduler tick, so a numpy window scan
    would cost more than the decode step it is trying to save), with
    a 4-byte alignment walk rejecting the rare unaligned byte-level
    false match."""
    hist = np.ascontiguousarray(hist, np.int32)
    L = int(hist.size)
    buf = hist.tobytes()
    for n in range(n_max, n_min - 1, -1):
        if L < n + 2:       # need the pattern + an earlier occurrence
            continue        # with at least one continuation token
        pat = buf[(L - n) * 4:]
        # search region ends at element L-2: the match must sit
        # strictly before the trailing pattern itself
        hi = (L - 1) * 4
        off = buf.rfind(pat, 0, hi)
        while off >= 0 and off % 4:     # byte-, not element-aligned
            off = buf.rfind(pat, 0, off + len(pat) - 1)
        if off < 0:
            continue
        start = off // 4 + n            # most recent occurrence
        cont = hist[start:start + k]
        if not cont.size:
            continue
        row = np.full(k, -1, np.int32)
        row[:cont.size] = cont
        return row
    return None


def make_prefill_fn(plan, ctx, pb: int, cache_dtype, *,
                    page_size: Optional[int] = None,
                    full_ctx: bool = True):
    """The engine's bucketed-prefill program for bucket length ``pb``
    (un-compiled jitted function; module-level for the same exporter
    single-source reason as :func:`make_decode_fn`).

    BOTH layouts take a traced ``start``: the program processes only the
    ``new_len`` tokens AFTER the ``start`` offset, continuing from
    whatever state the slot already holds.  On the paged side that is
    the shared-prefix half of the paged cache (the prefix-cache hit:
    attend through the page table to pages an earlier request already
    prefilled, prefill only the tail — the bucket is sized by the
    tail).  On BOTH sides it is what makes **chunked prefill** a plain
    bucket call: a long prompt is fed as a sequence of bounded slices,
    each continuing at the previous slice's ``start``, interleaved with
    decode steps (docs/serving.md "Overload survival") — no new program
    kind, the compile counters stay flat.  Positions are global
    throughout (RoPE, masks, KV scatter, sampling-key folds), so the
    emitted token stream is bitwise identical to a single unchunked
    prefill.

    Dense form, ``full_ctx=True`` (the chunk-capable convention, and
    the one v3 artifacts seal): the slot's full ``(1, l_max)`` rows are
    sliced out of the batch caches, the slice scans its positions
    against them (a ``start > 0`` continuation must attend every
    earlier position), and the rows splice back.  ``start == 0`` resets
    recurrent carried state in-program (a traced select — the slot rows
    may hold a previous occupant's carry); pad steps revert the WHOLE
    carried tree, so a pad position's clamped scatter can never clobber
    a real row.

    ``full_ctx=False`` (static; dense only) is the bucket-local fast
    path for whole-tail admissions — the caller guarantees
    ``start == 0``: the scan runs against a FRESH ``(1, pb)`` local
    cache (each of the ``pb`` steps attends at most ``pb`` positions,
    not ``l_max`` — a short prompt on a long-context engine must not
    pay O(l_max) attention per token just because chunking exists) and
    splices its ``pb``-length slab into the slot's rows.  Bitwise: at
    ``start == 0`` the two variants differ only in cache positions
    beyond the prompt, which the causal mask guarantees are never
    attended before decode rewrites them."""

    if page_size is not None:
        return _make_paged_prefill_fn(plan, ctx, pb, page_size)
    from .generate import _rec_state_init

    def prefill(params, caches, toks, prompt, new_len, start, slot,
                temp, topk, topp, key_data):
        if full_ctx:
            local = jax.tree.map(
                lambda big: jax.lax.dynamic_slice(
                    big, (slot,) + (jnp.int32(0),) * (big.ndim - 1),
                    (1,) + big.shape[1:]),
                caches)
            for key, u in plan._rec_units:
                init = _rec_state_init(u, 1)
                local[key] = jax.tree.map(
                    lambda i, o: jnp.where(start == 0,
                                           i.astype(o.dtype), o),
                    init, local[key])
        else:
            # whole-tail admission at start == 0: fresh bucket-length
            # rows (KV length pb, recurrent carry at its reset state —
            # exactly the start == 0 select above resolves to)
            local = plan.init_caches(params, 1, pb, cache_dtype)

        def body(carry, i):
            local = carry
            pos = start + i                     # global position
            tok = prompt[:, i]
            # plan.step REBINDS the dict's top-level entries in
            # place — hand it a shallow copy so ``local`` still
            # holds the pre-step leaves the gate needs
            logits, new = plan.step(params, dict(local), tok, pos, ctx)
            # pad positions (i >= new_len) must not advance carried
            # state, write KV, or — via the update-slice clamp at the
            # cache edge — clobber a real position: revert everything
            valid = i < new_len
            local = jax.tree.map(
                lambda n, o: jnp.where(valid, n, o), new, local)
            return local, logits

        local, ys = jax.lax.scan(body, local, jnp.arange(pb))
        last = jax.lax.dynamic_index_in_dim(
            ys, new_len - 1, 0, keepdims=False)         # (1, V)
        # the fold position is GLOBAL (start + new_len - 1): bitwise
        # the key an unchunked prefill of the whole prompt folds
        key = jax.random.fold_in(
            jax.random.wrap_key_data(key_data), start + new_len - 1)
        first = _sample_slots(
            last, key[None], temp[None], topk[None], topp[None])[0]
        # splice the slot's advanced rows back into the engine batch
        caches = jax.tree.map(
            lambda big, loc: jax.lax.dynamic_update_slice(
                big, loc.astype(big.dtype),
                (slot,) + (jnp.int32(0),) * (loc.ndim - 1)),
            caches, local)
        # like the paged path, the prompt region of ``toks`` is never
        # written (retire assembles from the request's own prompt);
        # only the sampled first token lands, at its global position —
        # an intermediate chunk's sample is overwritten by nothing and
        # read by nothing (decode starts at the FINAL chunk's sample)
        toks = toks.at[slot, start + new_len].set(first)
        return caches, toks, first

    return jax.jit(prefill, donate_argnums=(1, 2))


def _make_paged_prefill_fn(plan, ctx, pb: int, psz: int):
    """Paged prefill for bucket length ``pb`` (see
    :func:`make_prefill_fn`): ``prompt`` holds the ``new_len`` un-shared
    tail tokens, ``start`` the global position of the first one (a page
    multiple — the prefix-cache hit boundary), ``ptab_row`` the slot's
    complete page table (shared prefix pages + freshly allocated private
    pages; unassigned logical pages point at the scratch row).  Attention
    KV lands directly in the pool; recurrent carried state scans a local
    B=1 copy and splices into the engine batch like the dense path.
    ``start`` is either the prefix-cache hit boundary (a page multiple)
    or a chunked-prefill slice boundary — any earlier position whose KV
    the slot's pages already hold (docs/serving.md "Overload
    survival").  NOTE: recurrent state is position-recurrent from token
    0, so chains with recurrent units never take PREFIX shortcuts — the
    engine admits them with prefix_start=0 (enforced host-side in
    ``_reserve_pages``); chunk boundaries instead carry the state
    across slices (see the in-body comment)."""
    from .generate import _rec_state_init
    attn_keys = plan.attn_keys()

    def prefill(params, caches, toks, ptab_row, prompt, new_len, start,
                slot, temp, topk, topp, key_data):
        work = dict(caches)
        for key, u in plan._rec_units:
            # start == 0 resets the carry in-program (fresh admission);
            # start > 0 CONTINUES from the slot's batch rows — the
            # previous chunk's splice — which is what makes chunked
            # prefill exact for recurrent chains too.  (Prefix-cache
            # shortcuts still never apply to recurrent chains: the
            # scheduler admits them with prefix_start = 0, so a start>0
            # here is always a chunk boundary.)
            init = _rec_state_init(u, 1)
            cur = jax.tree.map(
                lambda big: jax.lax.dynamic_slice(
                    big, (slot,) + (jnp.int32(0),) * (big.ndim - 1),
                    (1,) + big.shape[1:]),
                caches[key])
            work[key] = jax.tree.map(
                lambda i, o: jnp.where(start == 0, i, o.astype(i.dtype)),
                init, cur)

        def body(carry, i):
            work = carry
            pos = start + i                     # global position
            # pad steps (i >= new_len) must neither advance carried
            # state nor write KV: attention writes route to the scratch
            # pool row, recurrent state is where-gated below
            valid = i < new_len
            logits, new = plan.step(
                params, dict(work), prompt[:, i], pos[None], ctx,
                pages=(ptab_row[None], psz, valid[None]))
            out = {}
            for k in new:
                if k in attn_keys:
                    out[k] = new[k]             # pool: scratch-gated
                else:
                    out[k] = jax.tree.map(
                        lambda n, o: jnp.where(valid, n, o),
                        new[k], work[k])
            return out, logits

        work, ys = jax.lax.scan(body, work, jnp.arange(pb))
        last = jax.lax.dynamic_index_in_dim(
            ys, new_len - 1, 0, keepdims=False)         # (1, V)
        # the fold position is GLOBAL (start + new_len - 1 == P - 1):
        # bitwise the key a dense prefill of the whole prompt folds
        key = jax.random.fold_in(
            jax.random.wrap_key_data(key_data), start + new_len - 1)
        first = _sample_slots(
            last, key[None], temp[None], topk[None], topp[None])[0]
        out_caches = dict(caches)
        for k in work:
            if k in attn_keys:
                out_caches[k] = work[k]
            else:  # splice the slot's fresh recurrent state into the batch
                out_caches[k] = jax.tree.map(
                    lambda big, loc: jax.lax.dynamic_update_slice(
                        big, loc.astype(big.dtype),
                        (slot,) + (jnp.int32(0),) * (loc.ndim - 1)),
                    caches[k], work[k])
        toks = toks.at[slot, start + new_len].set(first)
        return out_caches, toks, first

    return jax.jit(prefill, donate_argnums=(1, 2))


class ServeGeometry(NamedTuple):
    """Resolved serving geometry (see :func:`resolve_serve_geometry`).
    ``paged`` selects the page-pool KV layout; ``pages`` is 0 when
    dense.  ``n_ptab`` (= l_max // page_size) is the per-slot page-table
    width — the number of logical pages a max-length request spans.
    ``paged_kernel`` routes paged attention reads through the fused
    Pallas kernel (bounded-error; only meaningful when ``paged``).
    ``megastep`` is the decode micro-steps fused per dispatch (1 =
    plain per-token stepping; see :func:`make_megastep_fn`)."""
    slots: int
    l_max: int
    bucket_min: int
    paged: bool
    page_size: int
    pages: int
    paged_kernel: bool = False
    megastep: int = 1

    @property
    def n_ptab(self) -> int:
        return self.l_max // self.page_size if self.paged else 0


def resolve_serve_geometry(slots=None, l_max=None, bucket_min=None,
                           paged=None, page_size=None, pages=None,
                           paged_kernel=None, megastep=None):
    """Slot-batch geometry with ``root.common.serve`` defaults — ONE
    resolution shared by the live engine and the compiled-artifact
    exporter (export/compiled.py), so a default-configured export's
    bucket inventory is exactly what a default-configured engine
    compiles.

    Paged knobs (``root.common.serve.{paged, page_size, pages}``): the
    default pool (``slots * l_max / page_size`` pages) matches the dense
    layout's HBM exactly; serving MORE concurrent requests in the same
    memory means raising ``slots`` while holding ``pages`` — the pool,
    not ``slots * l_max``, is then the real token capacity.  A
    default ``page_size`` that does not divide ``l_max`` halves itself
    until it does (an explicit one must divide, or the page table could
    not tile the sequence)."""
    serve = root.common.serve
    slots = int(slots if slots is not None else serve.get("slots", 8))
    l_max = int(l_max if l_max is not None else serve.get("l_max", 512))
    bucket_min = max(1, int(bucket_min if bucket_min is not None
                            else serve.get("prefill_bucket_min", 16)))
    if slots < 1 or l_max < 2:
        raise ValueError("need slots >= 1 and l_max >= 2")
    use_paged = bool(serve.get("paged", True) if paged is None else paged)
    psz = int(page_size if page_size is not None
              else serve.get("page_size", 16))
    # the fused Pallas read path only exists for the paged layout: an
    # EXPLICIT request on a dense geometry is a loud misconfiguration;
    # the config default merely doesn't apply (so a dense artifact
    # still loads under a paged_kernel-on config)
    use_kernel = bool(serve.get("paged_kernel", False)
                      if paged_kernel is None else paged_kernel)
    mega = int(serve.get("megastep", 1)
               if megastep is None else megastep)
    if mega < 1:
        raise ValueError(
            f"serve.megastep must be >= 1, got {mega}")
    if not use_paged:
        if paged_kernel:
            raise ValueError(
                "paged_kernel requires the paged KV layout "
                "(root.common.serve.paged / paged=True)")
        return ServeGeometry(slots, l_max, bucket_min, False, psz, 0,
                             False, mega)
    if psz < 1:
        raise ValueError(f"page_size must be >= 1, got {psz}")
    if l_max % psz:
        if page_size is not None:
            raise ValueError(
                f"page_size {psz} must divide l_max {l_max} (the page "
                "table tiles the sequence in whole pages)")
        while l_max % psz:  # default page size adapts to small l_max
            psz //= 2
    n_ptab = l_max // psz
    if pages is None:
        pages = serve.get("pages", None)     # config None = dense-equiv
    pages = int(pages) if pages is not None else slots * n_ptab
    if pages < n_ptab:
        raise ValueError(
            f"page pool of {pages} pages cannot hold one max-length "
            f"request ({n_ptab} pages of {psz} tokens for l_max {l_max})")
    return ServeGeometry(slots, l_max, bucket_min, True, psz, pages,
                         use_kernel, mega)


def prefill_bucket(p: int, bucket_min: int, l_max: int) -> int:
    """THE bucket function: pow2 ceiling of prompt length ``p``, floored
    at ``bucket_min``, clipped to ``l_max``.  The live lookup and the
    exporter's inventory (:func:`bucket_table`) must agree, or an
    ArtifactRunner request maps to a bucket absent from the sealed
    program set."""
    return min(1 << max(0, math.ceil(math.log2(max(p, bucket_min)))),
               l_max)


def bucket_table(bucket_min: int, l_max: int):
    """The fixed prefill-bucket set a (bucket_min, l_max) engine can ever
    request — the compiled-artifact manifest's program inventory (one
    exported prefill per entry)."""
    return sorted({prefill_bucket(p, bucket_min, l_max)
                   for p in range(1, l_max + 1)})


class _StreamHandle:
    """Incremental token feed for ONE streaming request (docs/serving.md
    "Streaming and mid-stream failover").  The scheduler thread is the
    only producer: it pushes monotonically numbered frames — the index
    is the GLOBAL generated-token index, so a resume seeded from an
    emitted prefix numbers its first frame exactly one past the last
    frame the interrupted run delivered, and the router can splice the
    two streams gaplessly.  The consumer drains via :meth:`events`,
    which always ends with exactly one terminal event (the engine
    closes the handle from ``_observe_finish``, which every terminal
    edge reaches).  The buffer is bounded: a consumer that stops
    draining gets its stream closed with an overflow error instead of
    growing host memory — the request itself still retires unary."""

    __slots__ = ("_cond", "_frames", "next_i", "prompt_tokens",
                 "buffer_tokens", "closed", "finish_reason", "error",
                 "overflowed")

    def __init__(self, start_i: int, prompt_tokens: int,
                 buffer_tokens: int):
        self._cond = threading.Condition()
        self._frames = collections.deque()  # pending (i, token)  # guarded-by: self._cond
        # next global generated index the engine will push; the
        # scheduler thread is the sole writer, so its own unlocked
        # reads are safe
        self.next_i = int(start_i)          # guarded-by: self._cond
        self.prompt_tokens = int(prompt_tokens)
        self.buffer_tokens = int(buffer_tokens)
        self.closed = False                 # guarded-by: self._cond
        self.finish_reason = None           # guarded-by: self._cond
        self.error: Optional[str] = None    # guarded-by: self._cond
        self.overflowed = False             # guarded-by: self._cond

    def push(self, start_i: int, tokens) -> int:
        """Producer: append frames numbered ``start_i`` onward, skipping
        indices already pushed (idempotent across prefill/flush overlap).
        Returns the number of frames actually appended."""
        n = 0
        with self._cond:
            if self.closed:
                return 0
            i = int(start_i)
            for t in tokens:
                if i >= self.next_i:
                    self._frames.append((i, int(t)))
                    self.next_i = i + 1
                    n += 1
                i += 1
            if self.buffer_tokens and len(self._frames) > self.buffer_tokens:
                # slow consumer: close the stream rather than stall the
                # scheduler or grow without bound; the unary result on
                # the request stays available
                self.overflowed = True
                self.closed = True
                self.finish_reason = "error"
                self.error = (f"stream buffer overflow: consumer left "
                              f"more than {self.buffer_tokens} frames "
                              "undrained (serve.stream.buffer_tokens)")
            self._cond.notify_all()
        return n

    def close(self, finish_reason: str, error: Optional[str] = None):
        """Producer: mark the stream terminal (first close wins)."""
        with self._cond:
            if not self.closed:
                self.closed = True
                self.finish_reason = finish_reason
                self.error = error
            self._cond.notify_all()

    def events(self, timeout_s: Optional[float] = None):
        """Consumer generator: every pending ``("token", i, tok)`` frame
        in index order, then exactly one ``("done", finish_reason,
        error)``.  ``timeout_s`` bounds the TOTAL wait for a live
        producer (a dead engine thread must not hang the consumer
        forever); expiry raises :class:`TimeoutError`."""
        end = None if timeout_s is None else time.monotonic() + timeout_s
        while True:
            with self._cond:
                while not self._frames and not self.closed:
                    rem = 1.0 if end is None \
                        else end - time.monotonic()
                    if rem <= 0:
                        raise TimeoutError(
                            "stream consumer timed out waiting for the "
                            "next frame")
                    self._cond.wait(min(rem, 1.0))
                frames = list(self._frames)
                self._frames.clear()
                closed = self.closed
                reason, err = self.finish_reason, self.error
            for i, t in frames:
                yield ("token", i, t)
            if closed:
                yield ("done", reason, err)
                return


class _Request:
    __slots__ = ("prompt", "n_steps", "temperature", "top_k", "top_p",
                 "eos_id", "key_data", "deadline", "done", "result",
                 "error", "submitted_at", "slot", "finished_at",
                 "page_row", "prefix_start", "page_hashes",
                 "trace_id", "admitted_at", "first_token_at", "bucket",
                 "priority", "batch", "gen", "preemptions",
                 "chunk_next", "chunk_first", "run_started_at", "_eff",
                 "stream", "stop_seqs", "stop_hit")

    def __init__(self, prompt, n_steps, temperature, top_k, top_p,
                 eos_id, key_data, deadline, priority: int = 0,
                 batch: bool = False):
        self.prompt = prompt            # (P,) np.int32
        self.n_steps = n_steps
        self.temperature = temperature
        self.top_k = top_k              # None or int
        self.top_p = top_p              # None or float
        self.eos_id = eos_id            # None or int
        self.key_data = key_data        # raw uint32 PRNG key data
        self.deadline = deadline        # absolute monotonic seconds
        self.done = threading.Event()
        self.result = None              # np.int32 tokens, prompt included
        self.error: Optional[Exception] = None
        self.submitted_at = time.monotonic()
        self.finished_at = None
        self.slot = None
        self.page_row = None            # paged: this request's page table
        self.prefix_start = 0           # paged: first un-shared position
        self.page_hashes = ()           # paged: chained full-page hashes
        # observability (runtime/metrics.py): one trace track per
        # request, host timestamps for the queue-wait/prefill/decode
        # span breakdown in GET /trace.json
        self.trace_id = next_trace_id()
        self.admitted_at = None         # FIRST admission (left the queue)
        self.first_token_at = None      # prefill returned (== TTFT end)
        self.bucket = None              # prefill bucket this request took
        # overload survival (docs/serving.md "Overload survival"):
        # request class (0 = highest), tokens already generated before a
        # preemption (a resume re-prefills prompt + gen so the final
        # stream is bitwise an uninterrupted run), chunked-prefill
        # progress, and the latest admission stamp (victim selection
        # prefers the youngest run — the one losing least progress)
        self.priority = int(priority)
        # batch lane (docs/serving.md "Batch lane"): trough-filler
        # class strictly below every interactive priority — admitted
        # only with headroom, first-preempted, excluded from SLO
        # histograms (the tracker snapshots whole registry histograms,
        # so exclusion must happen at observation time)
        self.batch = bool(batch)
        self.gen = np.empty(0, np.int32)
        self.preemptions = 0
        self.chunk_next = 0             # next global position to prefill
        self.chunk_first = 0            # where THIS admission's prefill
        #                                 began (metric labels use the
        #                                 whole tail's bucket, not the
        #                                 final slice's)
        self.run_started_at = None      # latest admission into a slot
        self._eff = None                # memoized effective prompt
        # streaming (docs/serving.md "Streaming and mid-stream
        # failover"): the per-request frame feed, optional stop
        # sequences (token-id arrays, stream-only), and whether a stop
        # sequence — not eos/length — ended the run
        self.stream: Optional["_StreamHandle"] = None
        self.stop_seqs = ()
        self.stop_hit = False

    @property
    def end_index(self) -> int:
        """Global index of the FINAL token (invariant across
        preemptions: original prompt length + n_steps - 1)."""
        return int(self.prompt.size) + int(self.n_steps) - 1

    def effective_prompt(self):
        """What an admission prefills: the original prompt plus every
        token generated before a preemption."""
        if self._eff is None:
            self._eff = (np.concatenate([self.prompt, self.gen])
                         if self.gen.size else self.prompt)
        return self._eff

    def finish(self, result=None, error=None):
        self.result, self.error = result, error
        self.finished_at = time.monotonic()
        self.done.set()


class _PrioQueue:
    """Strict-priority FIFO over ``priorities`` classes (0 = highest):
    FIFO within a class, pops always drain the highest class first —
    the queue-jump half of the priority contract.  NOT thread-safe on
    its own: every mutation happens under the engine's ``_qlock``; the
    scheduler's lock-free emptiness peeks read one deque's truthiness
    at a time (GIL-atomic, the same staleness contract as the single
    deque this replaces)."""

    __slots__ = ("_qs",)

    def __init__(self, priorities: int):
        self._qs = [collections.deque()
                    for _ in range(max(1, int(priorities)))]

    def __len__(self):
        return sum(len(q) for q in self._qs)

    def __bool__(self):
        return any(self._qs)

    def __iter__(self):
        for q in self._qs:
            yield from q

    def append(self, req):
        self._qs[req.priority].append(req)

    def appendleft(self, req):
        self._qs[req.priority].appendleft(req)

    def popleft(self):
        for q in self._qs:
            if q:
                return q.popleft()
        return None

    def steal_lower(self, priority: int):
        """Evict and return the youngest NOT-YET-STARTED queued request
        of the LOWEST class strictly below ``priority``'s (class index
        strictly greater); None when nothing displaceable is queued —
        the full-queue queue-jump rule: a high-class arrival displaces
        the request that would have been served last anyway.  A
        PREEMPTED resume (``preemptions > 0``) is never displaced: it
        was accepted, held a slot, and carries committed device work in
        ``req.gen`` — finishing it with a 429 now would discard all of
        that and break the acceptance the 200-on-submit implied."""
        for c in range(len(self._qs) - 1, int(priority), -1):
            q = self._qs[c]
            for i in range(len(q) - 1, -1, -1):
                if q[i].preemptions == 0:
                    r = q[i]
                    del q[i]
                    return r
        return None

    def remove_if(self, pred):
        """Remove and return every queued request matching ``pred``
        (deadline sweeps), preserving order among the kept."""
        out = []
        for i, q in enumerate(self._qs):
            kept = collections.deque()
            for r in q:
                (out if pred(r) else kept).append(r)
            self._qs[i] = kept
        return out

    def clear(self):
        for q in self._qs:
            q.clear()


def _sample_slots(logits, keys, temp, top_k, top_p):
    """Per-slot next-token choice from (S, V) logits with per-slot
    traced sampling params — the batched twin of ``sample_logits``.

    Sentinels make a slot's filter a bitwise no-op exactly where the
    scalar path would SKIP it: ``top_k >= V`` clips to the minimum
    logit threshold (nothing filtered), ``top_p = 1.0`` cuts at the last
    sorted position (same), ``temp <= 0`` selects the greedy argmax.
    The op ORDER mirrors sample_logits: scale, top-k filter, top-p cut
    on the filtered logits, categorical; each slot draws its gumbel
    noise from its own key at shape (1, V) — the exact draw a B=1
    ``generate()`` makes, so single-row results are bitwise identical.
    """
    lg = logits.astype(jnp.float32)
    S, V = lg.shape
    greedy = jnp.argmax(lg, axis=-1)

    def do_sample():
        x = lg / jnp.where(temp > 0, temp, 1.0)[:, None]
        # top-k: k-th largest value as threshold
        # (== lax.top_k(...)[0][:,-1])
        srt = jnp.sort(x, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            srt, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
        x2 = jnp.where(x < kth, -jnp.inf, x)
        # top-p on the top-k-FILTERED logits (sample_logits order)
        srt2 = jnp.sort(x2, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt2, axis=-1)
        csum = jnp.cumsum(probs, axis=-1) - probs
        cut = jnp.maximum(
            jnp.sum(jnp.where(csum < top_p[:, None], 1, 0), axis=-1) - 1,
            0)
        thresh = jnp.take_along_axis(srt2, cut[:, None], axis=-1)
        x3 = jnp.where(x2 < thresh, -jnp.inf, x2)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row[None, :])[0])(
                keys, x3)
        return jnp.where(temp > 0, sampled, greedy)

    # all-greedy steps skip the sort/softmax/gumbel machinery entirely
    # (a runtime branch, not a trace-time one: the program stays fixed)
    return jax.lax.cond(
        (temp > 0).any(), do_sample, lambda: greedy).astype(jnp.int32)


class DecodeEngine(Logger):
    """Continuous-batching decode engine over a :class:`DecodePlan`.

    ``slots`` / ``l_max`` / ``window_ms`` / ``queue_depth`` /
    ``deadline_s`` / ``prefill_bucket_min`` default from
    ``root.common.serve.*`` (docs/serving.md).  Requests are single
    sequences; :meth:`generate` is the batch-blocking convenience with
    the ``generate()`` contract, :meth:`submit` the async primitive the
    REST layer drives.
    """

    def __init__(self, workflow, wstate, *, slots: Optional[int] = None,
                 l_max: Optional[int] = None,
                 window_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 output_unit: Optional[str] = None,
                 cache_dtype=jnp.float32, status=None,
                 paged: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 pages: Optional[int] = None,
                 paged_kernel: Optional[bool] = None,
                 spec: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_drafter: Optional[str] = None,
                 megastep: Optional[int] = None,
                 priorities: Optional[int] = None,
                 preempt: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 admission: Optional[AdmissionController] = None):
        self.workflow = workflow
        self.wstate = wstate
        self._init_config(slots=slots, l_max=l_max, window_ms=window_ms,
                          queue_depth=queue_depth, deadline_s=deadline_s,
                          paged=paged, page_size=page_size, pages=pages,
                          paged_kernel=paged_kernel, spec=spec,
                          spec_k=spec_k, spec_drafter=spec_drafter,
                          megastep=megastep,
                          priorities=priorities, preempt=preempt,
                          prefill_chunk=prefill_chunk,
                          admission=admission)
        self.plan = DecodePlan(workflow, output_unit)
        self.cache_dtype = cache_dtype
        self._ctx = Context(train=False, key=None, mesh=None)
        self.step_cache = StepCache()
        self.status = status
        # recurrent carried state is position-recurrent from token 0 and
        # is NOT paged, so prefix shortcuts are attention-only chains'
        # win (ArtifactRunner reads the same fact off the manifest)
        self._prefix_ok = not self.plan._rec_units
        self._init_runtime(wstate["params"])

    def _init_config(self, *, slots, l_max, window_ms, queue_depth,
                     deadline_s, bucket_min=None, paged=None,
                     page_size=None, pages=None, paged_kernel=None,
                     spec=None, spec_k=None, spec_drafter=None,
                     megastep=None, priorities=None, preempt=None,
                     prefill_chunk=None, admission=None):
        serve = root.common.serve
        geo = resolve_serve_geometry(slots, l_max, bucket_min,
                                     paged=paged, page_size=page_size,
                                     pages=pages,
                                     paged_kernel=paged_kernel,
                                     megastep=megastep)
        self.slots, self.l_max, self.bucket_min = \
            geo.slots, geo.l_max, geo.bucket_min
        self.paged, self.page_size, self.pages = \
            geo.paged, geo.page_size, geo.pages
        self.n_ptab = geo.n_ptab
        self.paged_kernel = geo.paged_kernel
        # megastep decode (docs/serving.md "Megastep decode"): N decode
        # micro-steps per dispatch; 1 = the plain per-token loop and no
        # fourth program is compiled at all
        self.megastep = geo.megastep
        self.window_s = float(window_ms if window_ms is not None
                              else serve.get("window_ms", 2.0)) / 1e3
        self.queue_depth = int(queue_depth if queue_depth is not None
                               else serve.get("queue_depth", 64))
        self.deadline_s = float(deadline_s if deadline_s is not None
                                else serve.get("deadline_s", 120.0))
        # overload survival (docs/serving.md "Overload survival"):
        # request classes (0 = highest; priorities=1 turns the feature
        # off), preemption of strictly-lower classes, and chunked
        # prefill (0 = off; slices of this many tokens interleave with
        # decode steps so one long prompt costs everyone bounded delay)
        self.priorities = max(1, int(serve.get("priorities", 3)
                                     if priorities is None
                                     else priorities))
        self.preempt = bool(serve.get("preempt", True)
                            if preempt is None else preempt)
        self.prefill_chunk = int(serve.get("prefill_chunk", 256)
                                 if prefill_chunk is None
                                 else prefill_chunk)
        # streaming (docs/serving.md "Streaming and mid-stream
        # failover"): how many undrained frames a consumer may leave
        # buffered before its stream is closed with an overflow error
        self.stream_buffer_tokens = int(
            serve.stream.get("buffer_tokens", 4096))
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}")
        # calling-convention / capability flags the ArtifactRunner
        # overrides from its manifest: whether the prefill programs take
        # the traced ``start`` (live builders always do; sealed dense
        # programs from older exports do not) and whether mid-prompt
        # continuation — chunked prefill — is safe on them
        self._prefill_start = True
        self._chunk_capable = True
        self._admission_arg = admission
        # speculative decoding (docs/serving.md "Speculative decoding"):
        # the host-side drafter proposes up to spec_k tokens per slot
        # and the third program kind verifies them in one call
        self.spec = bool(serve.spec.get("enabled", False)
                         if spec is None else spec)
        self.spec_k = int(serve.spec.get("k", 4)
                          if spec_k is None else spec_k)
        self.spec_drafter = str(serve.spec.get("drafter", "ngram")
                                if spec_drafter is None else spec_drafter)
        if self.spec:
            if self.spec_k < 1:
                raise ValueError(
                    f"serve.spec.k must be >= 1, got {self.spec_k}")
            if self.spec_drafter != "ngram":
                raise ValueError(
                    f"unknown speculative drafter "
                    f"{self.spec_drafter!r} (supported: 'ngram')")

    def _init_runtime(self, params):  # not-shared: __init__-only construction, precedes any thread
        """Slot state + scheduler + gauges + the AOT decode program —
        everything downstream of the three program hooks
        (:meth:`_make_caches` / :meth:`_head_width` /
        :meth:`_compile_decode`), which the artifact runner
        (runtime/artifact.py) overrides to serve deserialized StableHLO
        instead of freshly traced model code."""
        self._caches = self._make_caches(params)
        self._toks = jnp.zeros((self.slots, self.l_max), jnp.int32)
        # host-side per-slot metadata, passed into the compiled step
        S = self.slots
        self._pos = np.zeros(S, np.int32)       # index of last written tok
        self._active = np.zeros(S, bool)
        self._temp = np.zeros(S, np.float32)
        self._topk = np.zeros(S, np.int32)      # sentinel: V (keeps all)
        self._topp = np.ones(S, np.float32)     # sentinel: 1.0
        self._eos = np.full(S, -1, np.int32)    # sentinel: -1 (never hits)
        self._end = np.zeros(S, np.int32)       # final token index
        kd = jax.random.key_data(jax.random.key(0))
        self._keys = np.zeros((S,) + kd.shape, kd.dtype)
        self._slot_req: list = [None] * S

        # paged pool bookkeeping (host side; the device only ever sees
        # the int32 page table): refcounted physical pages, a chained
        # content-hash prefix index over full prompt pages, an LRU tick
        # for cached-page eviction, and the pool gauges
        if self.paged:
            self._scratch = self.pages          # pool row absorbing
            #                                     masked-off writes
            # _ptab is scheduler-thread-owned (written in _prefill,
            # read by _step_once); only the refcount/index structures
            # and pool gauges below cross threads via submit()/stats()
            self._ptab = np.full((S, self.n_ptab), self._scratch,
                                 np.int32)
            self._page_lock = threading.Lock()
            self._page_ref = np.zeros(self.pages, np.int32)  # guarded-by: self._page_lock
            self._page_free = list(range(self.pages))  # guarded-by: self._page_lock
            self._prefix_index: dict = {}  # guarded-by: self._page_lock
            self._page_key: dict = {}      # guarded-by: self._page_lock
            self._page_tick = np.zeros(self.pages, np.int64)  # guarded-by: self._page_lock
            self._tick = 0                 # guarded-by: self._page_lock
            self._prefix_hit_pages = 0     # guarded-by: self._page_lock
            self._prefix_miss_pages = 0    # guarded-by: self._page_lock
            self._evictions = 0            # guarded-by: self._page_lock
            self._cow_admissions = 0       # guarded-by: self._page_lock
            self._pool_rejected = 0        # guarded-by: self._page_lock
            # KV-page transfer (docs/serving.md "Disaggregated
            # prefill/decode"): which resident pages arrived over the
            # wire (import_pages) rather than from a local prefill, so
            # prefix hits on them can be attributed to the transfer
            self._imported_pages: set = set()  # guarded-by: self._page_lock
            self._remote_hit_pages = 0     # guarded-by: self._page_lock
            self._kv_exported_pages = 0    # guarded-by: self._page_lock
            self._kv_imported_pages = 0    # guarded-by: self._page_lock
            self._kv_export_bytes = 0      # guarded-by: self._page_lock
            self._kv_import_bytes = 0      # guarded-by: self._page_lock

        # staged KV-page imports: parsed+validated blobs wait here for
        # the scheduler to apply them at a decode-step boundary (the
        # same discipline as the swap double buffer — the scheduler
        # thread owns every _caches write).  Defined for dense engines
        # too (always empty there: import_pages rejects before staging).
        self._kv_imports: collections.deque = collections.deque()  # guarded-by: self._kv_import_lock
        self._kv_import_lock = threading.Lock()
        # wire-format identity: same-architecture weight sets share the
        # signature hash; the swap counter separates weight VERSIONS so
        # a blob exported before a hot swap can never contaminate the
        # post-swap prefix index (kv_wver property)
        self._kv_sig = hashlib.sha256(
            repr(tree_signature(params)).encode()).hexdigest()[:12]
        self._kv_entry_cache = None     # lazy _kv_entries() memo
        self._prefill_tok_s = 0.0       # scheduler-thread-written

        # queue + scheduler (priority-FIFO: class 0 pops first).  One
        # extra INTERNAL class beyond the configured interactive range
        # holds batch-lane work (docs/serving.md "Batch lane"): index
        # self.priorities, strictly below every submittable priority,
        # so victim selection preempts batch first and displacement
        # sheds queued batch first — with no code path treating batch
        # as anything but "just another (lowest) class".
        self._queue: _PrioQueue = _PrioQueue(self.priorities + 1)  # guarded-by: self._qlock
        self._qlock = threading.Lock()
        self._shed_by_class: dict = {}  # guarded-by: self._qlock
        # streaming: the live stream handles — the backing set of the
        # "stream-handles" resource pair (analysis/registry.py): every
        # _acquire_stream is balanced by a _release_stream on every
        # terminal edge via _observe_finish
        self._streams: set = set()      # guarded-by: self._qlock
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # chunked prefill: slots whose admission is mid-prefill (one
        # bounded slice per scheduler iteration, interleaved with
        # decode steps).  Scheduler-thread state like _ptab.
        self._chunking: set = set()
        self._qwait_ewma = 0.0          # scheduler-thread-written

        # hot-swap double buffer + drain mode (runtime/deploy.py)
        self._swap_lock = threading.Lock()
        self._staged = None  # (placed params, applied event)  # guarded-by: self._swap_lock
        self._swaps = 0
        self._draining = False
        self._died = False              # scheduler crashed (work FAILED)

        # gauges: per-engine views over the process-global metrics
        # registry (runtime/metrics.py) — stats(), status.json, GET
        # /engine and GET /metrics all read the SAME increments
        self._init_metrics()
        self._admitted = ScopedCounter(self._m_admitted)
        self._retired = ScopedCounter(self._m_retired)
        self._rejected = ScopedCounter(self._m_rejected)
        self._timeouts = ScopedCounter(self._m_timeouts)
        self._decode_steps = ScopedCounter(self._m_decode_steps)
        self._dispatches = ScopedCounter(self._m_dispatches)
        self._tok_count = ScopedCounter(self._m_tokens)
        self._occupancy_sum = 0
        self._rate_mark = (time.monotonic(), 0)
        self._tokens_per_sec = 0.0
        self._status_mark = 0.0
        # rolling SLO windows over the request histograms: the scheduler
        # tick rotates the ring (runtime/slo.py)
        self._slo = slo_tracker()
        # overload reflexes (runtime/admission.py): preemption counter
        # view + the SLO-driven admission-window controller, whose
        # sensor is the tracker's windowed burn rate.  Injectable for
        # deterministic tests (``admission=``).
        self._preempted = ScopedCounter(self._m_preempt)
        # batch lane: preemption counter view + a dedicated token rate
        # (scheduler-thread-written, published by _publish_gauges)
        self._batch_preempted = ScopedCounter(self._m_batch_preempt)
        self._batch_tok_n = 0           # scheduler-thread-written
        self._batch_rate_mark = (time.monotonic(), 0)
        self._batch_tok_s = 0.0         # scheduler-thread-written
        self._admission = (self._admission_arg
                           if self._admission_arg is not None
                           else AdmissionController(
                               queue_depth=self.queue_depth,
                               priorities=self.priorities,
                               burn_fn=self._slo.max_burn,
                               gauge=self._g_admission))

        # head width (== logits' last dim), for the top_k no-op sentinel
        self._vocab = self._head_width(params)

        # the lifetime decode program, AOT-compiled up front (through
        # the persistent cache: a restarted server skips the backend
        # compile of every program it has built before)
        enable_persistent_cache()
        self._decode = self._compile_decode(params)

        # megastep decode: the fourth program kind, compiled only when
        # configured on (N > 1) — an N=1 engine never pays its compile.
        # _mega_steps/_mega_bytes are scheduler-thread state.
        self._mega = None
        self._mega_steps = 0            # scheduler-thread-written
        self._mega_bytes = 0.0
        self._g_megastep_n.set(self.megastep)
        if self.megastep > 1:
            self._mega = self._compile_megastep(params)
            self._mega_bytes = self.step_cache.program_cost(
                "megastep")["bytes_accessed"]

        # speculative decoding: the ONE verify program (static k — the
        # third and last program kind) plus the host-side token history
        # the n-gram drafter reads.  _hist/_spec_* are scheduler-thread
        # state like _ptab; only the ScopedCounter views cross threads.
        self._verify = None
        self._verify_steps = 0          # scheduler-thread-written
        self._spec_proposed = ScopedCounter(self._m_spec_proposed)
        self._spec_accepted = ScopedCounter(self._m_spec_accepted)
        self._spec_rate_mark = (time.monotonic(), 0, 0)
        self._spec_accept_rate = 0.0
        # the interleave policy's measured state (scheduler-thread):
        # verify-step wall EWMA (vs the decode EWMA below), a recent
        # accept-rate EWMA (optimistic start so the first drafts run
        # and measure), and an attempt counter so a parked/cold policy
        # probes occasionally instead of paying drafter + history-sync
        # overhead per tick (armed so the FIRST tick attempts)
        self._verify_wall_ewma = 0.0
        self._verify_bytes = 0.0
        self._accept_ewma = 1.0
        self._ticks_since_attempt = _SPEC_PROBE_TICKS
        self._spec_attempts = 0         # cold-phase attempt budget
        if self.spec:
            self._hist = np.zeros((S, self.l_max), np.int32)
            self._hist_pos = np.zeros(S, np.int32)  # hist valid to here
            self._verify = self._compile_verify(params)
            self._verify_bytes = self.step_cache.program_cost(
                "verify")["bytes_accessed"]

        # goodput denominators: the decode program's cost analysis per
        # execution (bandwidth-utilization numerator) and a wall-time
        # EWMA the scheduler updates each step
        dc = self.step_cache.program_cost("decode")
        self._decode_flops = dc["flops"]
        self._decode_bytes = dc["bytes_accessed"]
        self._step_wall_ewma = 0.0      # scheduler-thread-written
        self._bw_ewma = 0.0             # achieved bytes/s (decode AND
        #                                 verify steps feed it)
        self._last_step_at = 0.0        # scheduler-thread-written

        # the aval-derived component ledger (runtime/memory.py,
        # GET /memory.json): exact bytes of what this engine pinned
        self._register_memory()

    def _init_metrics(self):  # not-shared: __init__-only construction, precedes any thread
        """Register the serving metrics (idempotent: engines come and go
        within one process, the registry series live on — stats() stays
        per-engine through the ScopedCounter views).  Names are the
        contract docs/observability.md's reference table documents and
        the VM4xx analysis rule enforces."""
        reg = registry()
        self._m_queue_wait = reg.histogram(
            "vt_request_queue_wait_seconds",
            "time a request waited between submit() and the start of "
            "its prefill (admission into a slot)")
        self._m_ttft = reg.histogram(
            "vt_request_ttft_seconds",
            "submit-to-first-token latency, labelled by the prefill "
            "bucket the request took", labels=("bucket",))
        self._m_prefill = reg.histogram(
            "vt_prefill_seconds",
            "wall time of one prefill program call, labelled by bucket",
            labels=("bucket",))
        self._m_decode_step = reg.histogram(
            "vt_decode_step_seconds",
            "wall time of one decode step (all active slots advance one "
            "token) — the per-token decode latency under load")
        self._m_requests = reg.counter(
            "vt_requests_total",
            "finished requests by outcome: ok | 429 (overload/pool "
            "rejection) | 504 (deadline) | crash (scheduler died) | "
            "stopped (engine stopped with work pending)",
            labels=("outcome",))
        self._m_admitted = reg.counter(
            "vt_engine_admitted_total", "requests admitted into a slot")
        self._m_retired = reg.counter(
            "vt_engine_retired_total", "requests retired complete")
        self._m_rejected = reg.counter(
            "vt_engine_rejected_total",
            "requests refused at submit (queue overflow or page-pool "
            "exhaustion; the HTTP 429 path)")
        self._m_timeouts = reg.counter(
            "vt_engine_timeouts_total",
            "requests failed on their deadline (queued or mid-flight; "
            "the HTTP 504 path)")
        self._m_decode_steps = reg.counter(
            "vt_engine_decode_steps_total",
            "decode micro-steps executed (a megastep dispatch counts "
            "its N fused micro-steps)")
        self._m_dispatches = reg.counter(
            "vt_decode_dispatches_total",
            "host dispatches of a token-advancing program (decode, "
            "speculative verify, or megastep) — the megastep "
            "amortization divides this by ~N at constant tokens")
        self._g_megastep_n = reg.gauge(
            "vt_megastep_n",
            "configured decode micro-steps fused per megastep "
            "dispatch (1 = megastep off)")
        self._m_tokens = reg.counter(
            "vt_engine_tokens_total", "tokens generated")
        self._m_swaps = reg.counter(
            "vt_engine_swaps_total", "hot weight swaps applied")
        self._g_occupancy = reg.gauge(
            "vt_engine_occupancy", "slots currently decoding")
        self._g_queue_depth = reg.gauge(
            "vt_engine_queue_depth", "requests waiting in the queue")
        self._g_tokens_per_sec = reg.gauge(
            "vt_engine_tokens_per_sec",
            "recent decode throughput (0.5s window)")
        self._g_pages_used = reg.gauge(
            "vt_pages_used", "pool pages referenced by live slots")
        self._g_pages_cached = reg.gauge(
            "vt_pages_cached",
            "refcount-0 pages kept resident by the prefix index")
        self._g_pages_free = reg.gauge(
            "vt_pages_free", "pool pages on the free list")
        self._g_prefix_hit_rate = reg.gauge(
            "vt_prefix_hit_rate",
            "fraction of full prompt pages served from the prefix "
            "cache since engine start")
        # goodput (docs/observability.md "Goodput & MFU"): how close to
        # the hardware the decode loop actually runs
        self._g_decode_bw = reg.gauge(
            "vt_decode_bandwidth_bytes_per_sec",
            "achieved decode memory traffic: cost-analysis bytes over "
            "wall (EWMA), fed by decode AND speculative verify steps")
        self._g_decode_mbu = reg.gauge(
            "vt_decode_mbu",
            "decode model-bandwidth-utilization: achieved bytes/s over "
            "the device's published HBM peak (0 = not measured: no TPU)")
        self._g_tps_chip = reg.gauge(
            "vt_tokens_per_sec_per_chip",
            "recent decode throughput per local device")
        self._g_headroom = reg.gauge(
            "vt_memory_headroom_slots",
            "max-length requests the engine can still admit (free "
            "slots, bounded by free+evictable pages when paged)")
        # speculative decoding (docs/serving.md "Speculative decoding"):
        # proposal/acceptance volume plus the windowed accept rate that
        # decides whether the drafter is paying for its verify steps
        self._m_spec_proposed = reg.counter(
            "vt_spec_proposed_total",
            "draft tokens proposed to the speculative verify program")
        self._m_spec_accepted = reg.counter(
            "vt_spec_accepted_total",
            "draft tokens accepted (emitted token matched the proposal)")
        self._g_spec_accept_rate = reg.gauge(
            "vt_spec_accept_rate",
            "accepted/proposed draft tokens over the recent window "
            "(0.5s; 0 when nothing was proposed)")
        self._m_spec_verify = reg.histogram(
            "vt_spec_verify_step_seconds",
            "wall time of one speculative verify step (all active "
            "slots score k+1 positions in one call)")
        # overload survival (docs/serving.md "Overload survival"):
        # priority preemption volume, shed load by class, and the
        # admission controller's live window
        self._m_preempt = reg.counter(
            "vt_preemptions_total",
            "slots preempted (retired-and-requeued) so a higher-"
            "priority request could be admitted")
        self._m_shed = reg.counter(
            "vt_shed_total",
            "requests shed by the admission controller or displaced "
            "from a hard-full queue by a higher-priority arrival, by "
            "request class", labels=("priority",))
        self._g_admission = reg.gauge(
            "vt_admission_window",
            "admitted queue window the SLO-driven controller currently "
            "grants (== serve.queue_depth when fully open)")
        # KV-page transfer (docs/serving.md "Disaggregated
        # prefill/decode"): serialized prefix-page export/import volume
        # and the prefix hits that landed on imported pages
        self._m_kv_exported = reg.counter(
            "vt_kv_pages_exported_total",
            "prefix pages serialized out by export_pages "
            "(GET /kv/pages)")
        self._m_kv_imported = reg.counter(
            "vt_kv_pages_imported_total",
            "prefix pages deserialized into the pool by import_pages "
            "(PUT /kv/pages) — skipped duplicates and pool-full drops "
            "not included")
        self._m_kv_bytes = reg.counter(
            "vt_kv_transfer_bytes_total",
            "serialized KV-page wire bytes, by transfer direction",
            labels=("direction",))
        self._m_kv_seconds = reg.histogram(
            "vt_kv_transfer_seconds",
            "wall time of one export_pages / import_pages call "
            "(serialize or validate+apply; not the network leg), by "
            "direction", labels=("direction",))
        self._m_remote_hits = reg.counter(
            "vt_prefix_remote_hits_total",
            "prefix-cache page hits served by pages that arrived via "
            "KV-page import rather than a local prefill")
        # batch lane (docs/serving.md "Batch lane"): trough-filler
        # throughput and how often interactive traffic reclaimed its
        # slots.  Batch requests never touch the SLO histograms above —
        # exclusion happens at observation time.
        self._g_batch_tps = reg.gauge(
            "vt_batch_tokens_per_sec",
            "recent batch-lane decode throughput (0.5s window) — the "
            "trough goodput interactive SLOs never see")
        self._m_batch_preempt = reg.counter(
            "vt_batch_preemptions_total",
            "batch-lane slots preempted so interactive work could be "
            "admitted (subset of vt_preemptions_total)")
        # streaming (docs/serving.md "Streaming and mid-stream
        # failover"): engine-side frame volume and live handle count
        self._m_stream_frames = reg.counter(
            "vt_stream_frames_total",
            "token frames pushed to streaming consumers")
        self._g_stream_active = reg.gauge(
            "vt_stream_active",
            "stream handles currently open on this engine")

    def _register_memory(self):  # not-shared: __init__-only construction, precedes any thread
        """Publish this engine's aval-derived byte ledger (runtime/
        memory.py, GET /memory.json): params, the KV cache (page pool or
        dense rows), and the slot state (recurrent carries + token rows
        + page tables).  Exact shape*itemsize arithmetic — the same
        numbers on CPU and TPU, which is what makes the ledger testable
        where the device reports nothing.  ``stats()["memory"]`` reads
        the per-engine copy kept here, never the process ledger — two
        engines in one process (a bench A/B, a deploy reload) must not
        read each other's bytes; the process ledger keeps last-writer-
        wins for /memory.json and the finalizer drops this engine's
        stamped entries when its buffers are actually freed."""
        import weakref
        mem = memory_monitor()
        attn = self._attn_cache_keys()
        kv = {k: v for k, v in self._caches.items() if k in attn}
        rest = {k: v for k, v in self._caches.items() if k not in attn}
        slot_state = tree_bytes(rest) + tree_bytes(self._toks)
        if self.paged:
            slot_state += int(self._ptab.nbytes)
        self._mem_bytes = {
            "params": tree_bytes(self.wstate["params"]),
            "kv_cache": tree_bytes(kv),
            "slot_state": slot_state,
        }
        stamps = {f"engine.{k}": mem.set_component(f"engine.{k}", v)
                  for k, v in self._mem_bytes.items()}
        extra_stamp = mem.set_extra("engine", {
            "slots": self.slots, "l_max": self.l_max,
            "paged": self.paged,
            **({"pages": self.pages, "page_size": self.page_size}
               if self.paged else {}),
        })
        from .memory import drop_stamped_components
        self._mem_finalizer = weakref.finalize(
            self, drop_stamped_components, stamps,
            {"engine": extra_stamp})
        mem.ensure_poller()

    def _attn_cache_keys(self):
        """Cache keys backed by attention KV.  The live engine asks its
        DecodePlan; an ArtifactRunner (plan=None) classifies by the
        cache's own structure — attention entries are {"k", "v"} dicts,
        recurrent carries are {"h"(, "c")} — which the sealed rows
        preserve."""
        if self.plan is not None:
            return self.plan.attn_keys()
        return {k for k, v in self._caches.items()
                if isinstance(v, dict) and "k" in v and "v" in v}

    def _observe_finish(self, req, outcome: str):
        """Host-side request accounting at every terminal edge: the
        outcome counter plus the request's span-ring timeline
        (queue-wait → prefill → decode nested under one request span,
        one trace track per request id)."""
        # the stream handle (when one exists) closes at the SAME edge
        # the outcome counter observes — a streaming consumer always
        # gets exactly one terminal frame, whatever ended the request
        self._release_stream(req, outcome)
        self._m_requests.labels(outcome=outcome).inc()
        sub = req.submitted_at
        fin = req.finished_at if req.finished_at is not None \
            else time.monotonic()
        ring = span_ring()
        args = {"id": req.trace_id, "outcome": outcome,
                "prompt_tokens": int(req.prompt.size),
                "n_steps": int(req.n_steps)}
        if req.priority:
            args["priority"] = int(req.priority)
        if req.preemptions:
            args["preemptions"] = int(req.preemptions)
        if req.slot is not None:
            args["slot"] = int(req.slot)
        if req.bucket is not None:
            args["bucket"] = int(req.bucket)
        if self.paged and req.admitted_at is not None:
            args["prefix_start"] = int(req.prefix_start)
        ring.add("request", sub, fin - sub, cat="request",
                 tid=req.trace_id, args=args)
        if req.admitted_at is None:
            ring.add("queue_wait", sub, fin - sub, cat="serve",
                     tid=req.trace_id)
            return
        ring.add("queue_wait", sub, req.admitted_at - sub, cat="serve",
                 tid=req.trace_id)
        if req.first_token_at is not None:
            ring.add("prefill", req.admitted_at,
                     req.first_token_at - req.admitted_at, cat="serve",
                     tid=req.trace_id,
                     args={"bucket": int(req.bucket or 0)})
            ring.add("decode", req.first_token_at,
                     fin - req.first_token_at, cat="serve",
                     tid=req.trace_id)

    # -- stream handles (analysis/registry.py RESOURCE_PAIRS
    # "stream-handles"): acquired in submit(), released at every
    # terminal edge via _observe_finish -------------------------------------
    def _acquire_stream(self, req: _Request) -> _StreamHandle:
        """Open the request's frame feed and register it in the live
        set (``_streams``) — the VR7xx lifecycle rules prove every
        terminal edge releases it.  Frame numbering starts at the
        request's emitted-prefix size, so a failover resume continues
        the interrupted run's numbering."""
        h = _StreamHandle(int(req.gen.size), int(req.prompt.size),
                          self.stream_buffer_tokens)
        with self._qlock:
            self._streams.add(h)
            self._g_stream_active.set(len(self._streams))
        return h

    def _release_stream(self, req: _Request, outcome: str):
        """Close + unregister the request's stream handle (no-op for
        unary requests).  The terminal frame's finish reason maps from
        the request outcome: ok → stop/eos/length, 504 → deadline,
        everything else (shed, crash, stopped) → error."""
        h = req.stream
        if h is None:
            return
        err = None
        if outcome == "ok":
            gen_n = (0 if req.result is None
                     else int(req.result.size) - int(req.prompt.size))
            if req.stop_hit:
                reason = "stop"
            elif (req.eos_id is not None and gen_n
                    and gen_n < int(req.n_steps)
                    and int(req.result[-1]) == int(req.eos_id)):
                reason = "eos"
            else:
                reason = "length"
        elif outcome == "504":
            reason = "deadline"
            err = (str(req.error) if req.error is not None
                   else "request deadline expired")
        else:
            reason = "error"
            err = str(req.error) if req.error is not None else outcome
        h.close(reason, err)
        with self._qlock:
            self._streams.discard(h)
            self._g_stream_active.set(len(self._streams))

    # -- compiled programs --------------------------------------------------
    @staticmethod
    def _sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
            tree)

    def _make_caches(self, params):
        if self.paged:
            return self.plan.init_caches(
                params, self.slots, self.l_max, self.cache_dtype,
                kv_rows=self.pages + 1, page_size=self.page_size)
        return self.plan.init_caches(
            params, self.slots, self.l_max, self.cache_dtype)

    def _head_width(self, params) -> int:
        S = self.slots
        shallow = dict(self._caches)  # plan.step rebinds top-level keys
        pages_arg = None
        if self.paged:
            pages_arg = (jnp.zeros((S, self.n_ptab), jnp.int32),
                         self.page_size, jnp.zeros(S, bool))
        return int(jax.eval_shape(
            lambda p, c, t, pv: self.plan.step(p, c, t, pv, self._ctx,
                                               pages=pages_arg)[0],
            params, shallow, jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.int32)).shape[-1])

    def _decode_args_sds(self, params):
        args = (params, self._caches, self._toks)
        if self.paged:
            args += (self._ptab,)
        return self._sds(args + (self._pos, self._active, self._temp,
                                 self._topk, self._topp, self._eos,
                                 self._end, self._keys))

    def _prefill_args_sds(self, params, pb: int):
        z32 = np.int32(0)
        if self.paged:
            return self._sds((params, self._caches, self._toks,
                              self._ptab[0], np.zeros((1, pb), np.int32),
                              z32, z32, z32, np.float32(0), z32,
                              np.float32(1), self._keys[0]))
        if self._prefill_start:
            return self._sds((params, self._caches, self._toks,
                              np.zeros((1, pb), np.int32), z32, z32,
                              z32, np.float32(0), z32, np.float32(1),
                              self._keys[0]))
        # sealed dense artifacts from pre-chunking exports: the
        # whole-prompt calling convention (no traced start)
        return self._sds((params, self._caches, self._toks,
                          np.zeros((1, pb), np.int32), z32, z32,
                          np.float32(0), z32, np.float32(1),
                          self._keys[0]))

    def _geometry_key(self):
        """StepCache key suffix: everything shape-determining about the
        cache layout (a paged and a dense engine at the same slots/l_max
        are DIFFERENT programs, as are the gather and fused-kernel read
        paths)."""
        if self.paged:
            return (self.slots, self.l_max, "paged", self.page_size,
                    self.pages) + (("pkernel",) if self.paged_kernel
                                   else ())
        return (self.slots, self.l_max)

    def _compile_decode(self, params):
        psz = self.page_size if self.paged else None
        step, _, _ = self.step_cache.get_step(
            "decode", self._geometry_key(),
            lambda: (make_decode_fn(self.plan, self._ctx, self.slots,
                                    page_size=psz,
                                    paged_kernel=self.paged_kernel),
                     None, None),
            self._decode_args_sds(params), pin=(self.workflow,))
        return step

    def _verify_args_sds(self, params):
        return self._decode_args_sds(params) + (
            jax.ShapeDtypeStruct((self.slots, self.spec_k), jnp.int32),)

    def _compile_verify(self, params):
        psz = self.page_size if self.paged else None
        step, _, _ = self.step_cache.get_step(
            "verify", self._geometry_key() + ("k", self.spec_k),
            lambda: (make_verify_fn(self.plan, self._ctx, self.slots,
                                    self.spec_k, page_size=psz,
                                    paged_kernel=self.paged_kernel),
                     None, None),
            self._verify_args_sds(params), pin=(self.workflow,))
        return step

    def _compile_megastep(self, params):
        # same calling convention as the decode program; N joins the
        # StepCache key the way the verify program's k does, so two
        # engines at different N are different programs, never a
        # recompile of one
        psz = self.page_size if self.paged else None
        step, _, _ = self.step_cache.get_step(
            "megastep", self._geometry_key() + ("mega", self.megastep),
            lambda: (make_megastep_fn(self.plan, self._ctx, self.slots,
                                      self.megastep, page_size=psz,
                                      paged_kernel=self.paged_kernel),
                     None, None),
            self._decode_args_sds(params), pin=(self.workflow,))
        return step

    def _bucket(self, p: int) -> int:
        return prefill_bucket(p, self.bucket_min, self.l_max)

    def _prefill_fn(self, pb: int, params, full_ctx: bool = True):
        """Fetch/compile the prefill program for bucket length ``pb``.
        ``full_ctx=False`` (dense only — the paged program always works
        through the page table) selects the bucket-local fast variant
        for whole-tail ``start == 0`` admissions; chunk slices need the
        full-context form (see :func:`make_prefill_fn`)."""
        psz = self.page_size if self.paged else None
        full_ctx = True if self.paged else bool(full_ctx)
        step, _, _ = self.step_cache.get_step(
            "prefill", (pb, full_ctx) + self._geometry_key(),
            lambda: (make_prefill_fn(self.plan, self._ctx, pb,
                                     self.cache_dtype, page_size=psz,
                                     full_ctx=full_ctx),
                     None, None),
            self._prefill_args_sds(params, pb), pin=(self.workflow,))
        return step

    # -- public API ---------------------------------------------------------
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, name="decode-engine", daemon=True)
        self._thread.start()
        if self.paged:
            self.info(
                "decode engine: %d slots x L=%d over %d pages x %d "
                "tokens (paged, prefix reuse %s), queue %d",
                self.slots, self.l_max, self.pages, self.page_size,
                "on" if self._prefix_ok else "off", self.queue_depth)
        else:
            self.info("decode engine: %d slots x L=%d, queue %d",
                      self.slots, self.l_max, self.queue_depth)
        return self

    @property
    def started(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def stop(self):
        self._stop_evt.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=30)
            if t.is_alive():
                # a wedged scheduler must keep owning the slots: if we
                # forgot it here, a restart would spawn a SECOND
                # scheduler double-donating the same device buffers
                self.warning("scheduler did not exit within 30s; "
                             "engine cannot be restarted until it does")
                return
            self._thread = None

    # -- lifecycle ops: hot swap + drain (runtime/deploy.py drives these) ---
    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def swaps(self) -> int:
        return self._swaps

    def swap_params(self, params, *, timeout: Optional[float] = None):
        """Zero-downtime hot weight swap: stage ``params`` on device as a
        double buffer while the current version keeps serving, then flip
        the served tree atomically at a decode-step boundary.

        The new tree must match the live one leaf for leaf in path,
        shape and dtype — the compiled prefill/decode programs are
        reused as-is (the StepCache counters stay flat across a swap); a
        mismatched tree is rejected with a clear error and the old
        version keeps serving.  In-flight slots finish their current
        step on the old buffer; the next step reads the new one (their
        KV caches are model-version-mixed for the remainder of the
        sequence — the standard continuous-serving trade, documented in
        docs/serving.md).  Thread-safe; blocks until the flip happened
        or ``timeout`` (default ``root.common.serve.swap_timeout_s``)
        expired, in which case the staged buffer is withdrawn and the
        old version keeps serving.
        """
        if timeout is None:
            timeout = float(root.common.serve.get("swap_timeout_s", 60.0))
        old_sig = tree_signature(self.wstate["params"])
        new_sig = tree_signature(params)
        if old_sig != new_sig:
            raise ValueError(
                "hot swap rejected — parameter tree does not match the "
                "compiled programs (same-architecture weights only; a "
                "different architecture needs a fresh engine): "
                + signature_mismatch(old_sig, new_sig))
        # fully staged BEFORE the flip: the scheduler must never block
        # a decode step on an in-flight H2D transfer (no-op when the
        # caller pre-placed the tree, e.g. DeployController._stage)
        staged = place_like(params, self.wstate["params"])
        if not self.started:
            self.wstate = dict(self.wstate, params=staged)
            self._swaps += 1
            self._m_swaps.inc()
            self._invalidate_prefix_cache()
            return
        done = threading.Event()
        with self._swap_lock:
            if self._staged is not None:
                raise RuntimeError(
                    "another swap is already staged and not yet applied")
            self._staged = (staged, done)
        self._wake.set()
        if not done.wait(timeout):
            with self._swap_lock:
                if self._staged is not None and self._staged[1] is done:
                    self._staged = None
                    raise TimeoutError(
                        f"swap not applied within {timeout}s (scheduler "
                        "wedged?); the old version keeps serving")
            # the flip landed between the wait timeout and the lock

    def _apply_swap(self):
        """Scheduler-thread only: flip the served params to the staged
        buffer.  Called between decode steps, so no program is mid-step
        — in-flight slots see the new weights from their NEXT token."""
        with self._swap_lock:
            staged, self._staged = self._staged, None
        if staged is None:
            return
        params, done = staged
        self.wstate = dict(self.wstate, params=params)
        self._swaps += 1
        self._m_swaps.inc()
        # cached prefix pages hold KV computed under the OLD weights.
        # In-flight slots finishing on mixed versions is the documented
        # hot-swap trade, but a stale cached prefix would contaminate
        # arbitrarily many NEW requests (and every hit would renew its
        # LRU tick, so it would never age out) — drop the index now.
        self._invalidate_prefix_cache()
        done.set()

    def _invalidate_prefix_cache(self):
        """Unregister every cached prefix page (post-swap: their KV
        belongs to the previous weights).  Refcount-0 pages return to
        the free list; pages still referenced by in-flight slots keep
        serving THOSE slots and are freed by the normal release path
        once they retire (release checks registration at that point)."""
        if not self.paged:
            return
        with self._page_lock:
            for pid in list(self._page_key):
                del self._prefix_index[self._page_key.pop(pid)]
                if self._page_ref[pid] == 0:
                    self._page_free.append(pid)
            # imported pages hold peer KV computed under the OLD
            # weights too — same staleness, same drop
            self._imported_pages.clear()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: stop admissions (``submit`` raises
        :class:`EngineDraining` → the REST layer's 503), let queued and
        in-flight work retire, then stop the scheduler.  Returns True
        when everything retired before ``timeout`` (default
        ``root.common.serve.drain_timeout_s``); on timeout the engine
        stops anyway and leftovers fail with :class:`EngineStopped`."""
        if timeout is None:
            timeout = float(root.common.serve.get("drain_timeout_s", 30.0))
        self._draining = True
        deadline = time.monotonic() + max(0.0, float(timeout))
        while self.started and time.monotonic() < deadline:
            if self._idle():
                break
            time.sleep(0.01)
        # a crashed scheduler also leaves the slots/queue empty — but
        # via _fail_all, which FAILED the work rather than retiring it:
        # that is a dirty drain, never a clean one
        clean = not self._died and self._idle()
        self.stop()
        return clean

    def _idle(self) -> bool:
        """No queued, reserved, or decoding work anywhere.  _slot_req is
        part of the check because a request being prefilled is already
        out of the queue but not yet in _active — drain must not
        declare victory inside that window."""
        with self._qlock:
            queued = bool(self._queue)
        return (not self._active.any() and not queued
                and all(r is None for r in self._slot_req))

    def submit(self, prompt, n_steps: int, *, temperature: float = 0.0,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               eos_id: Optional[int] = None, key=None,
               deadline_s: Optional[float] = None,
               priority: int = 0, batch: bool = False,
               stream: bool = False, emitted_prefix=None,
               stop=None) -> _Request:
        """Enqueue one sequence; returns a request whose ``done`` event
        fires with ``result`` (np.int32, prompt + generated, trimmed at
        eos) or ``error``.

        ``stream=True`` opens an incremental frame feed on
        ``req.stream`` (a :class:`_StreamHandle`): consume
        ``req.stream.events()`` for monotonically numbered token frames
        plus exactly one terminal event (docs/serving.md "Streaming and
        mid-stream failover").  ``emitted_prefix`` is the crash-safe
        RESUME form: pass the ORIGINAL prompt, ORIGINAL ``n_steps`` and
        ORIGINAL ``key`` plus the tokens already emitted by an
        interrupted run, and the continuation is bitwise-identical to
        the uninterrupted run — greedy and sampled — because it rides
        the preemption harvest/re-prefill path, whose sampling keys
        fold in GLOBAL token positions.  Frames of a resume are
        numbered from ``len(emitted_prefix)``, so a router can splice
        the streams gaplessly.  ``stop`` (streaming only) is a list of
        token-id sequences: generation retires early — "stop" finish
        reason — when the generated tail matches one, even across a
        flush boundary.  Raises :class:`EngineOverloaded` when the
        queue is full or the admission controller shed the request (the
        REST layer's 429 with an adaptive Retry-After).  ``priority``
        is the request class, 0 (the default, highest) to
        ``priorities - 1``: higher classes pop first, may displace a
        queued lower-class request from a hard-full queue, may preempt
        a running lower-class slot, and are the last the controller
        sheds (docs/serving.md "Overload survival").

        ``batch=True`` rides the trough-filler class (docs/serving.md
        "Batch lane"): strictly below every interactive priority,
        admitted only while slot headroom and SLO burn leave room
        (429 "trough closed" otherwise), first-preempted when
        interactive traffic arrives, and excluded from the queue-wait/
        TTFT SLO histograms.  ``priority`` is ignored for batch."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        n_steps = int(n_steps)
        if n_steps < 1:
            raise ValueError("n_steps must be >= 1")
        pref = None
        if emitted_prefix is not None:
            pref = np.asarray(emitted_prefix, np.int32).reshape(-1)
            # strictly fewer than n_steps: at == the resume would have
            # nothing left to generate, yet prefill always samples one
            # token — it would emit one PAST the original end_index
            if pref.size >= n_steps:
                raise ValueError(
                    f"emitted_prefix holds {pref.size} tokens but "
                    f"n_steps is {n_steps}; the resume form needs at "
                    "least one token left to generate (pass the "
                    "ORIGINAL n_steps, not the remainder)")
        stop_seqs = ()
        if stop:
            stop_seqs = tuple(np.asarray(s, np.int32).reshape(-1)
                              for s in stop)
            if not stream:
                raise ValueError(
                    "stop sequences ride the streaming path (their "
                    "detection runs at flush time); pass stream=True")
            if len(stop_seqs) > 16:
                raise ValueError(
                    f"at most 16 stop sequences, got {len(stop_seqs)}")
            for s in stop_seqs:
                if not 1 <= s.size <= 32:
                    raise ValueError(
                        "each stop sequence must hold 1..32 tokens, "
                        f"got {s.size}")
        priority = int(priority)
        if batch:
            # the internal lowest class — index self.priorities, one
            # past the submittable range, reserved for the batch lane
            priority = self.priorities
        elif not 0 <= priority < self.priorities:
            raise ValueError(
                f"priority must be in [0, {self.priorities}) "
                f"(serve.priorities classes, 0 = highest), got {priority}")
        # same contract as sample_logits: out-of-domain filters must be
        # a loud 400, not a silently-degenerate sentinel (top_k=0 would
        # make the k-th threshold the MAX logit — greedy in disguise)
        if top_k is not None and int(top_k) < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if prompt.size + n_steps > self.l_max:
            raise ValueError(
                f"prompt {prompt.size} + n_steps {n_steps} exceeds the "
                f"engine's l_max {self.l_max}")
        if key is None:
            key = jax.random.key(0)
        if self._draining:
            # drain contract: in-flight and already-queued work retires,
            # NEW work is refused so the slot set empties (HTTP 503)
            raise EngineDraining(
                "engine is draining; not accepting new requests")
        if self._died:
            raise SchedulerCrashed(
                "engine scheduler crashed earlier; restart the engine "
                "(see the scheduler_crash status event for the cause)")
        if not self.started:
            # a dead scheduler (stopped, or its loop died) would leave
            # the request queued forever with nothing enforcing its
            # deadline — fail the caller loudly instead
            raise EngineStopped("engine is not running (call start())")
        if batch:
            # trough-filler admission: batch enters only while
            # interactive occupancy and SLO burn leave headroom — the
            # 429 tells the job manager to wait the burst out, not to
            # compete with it.  (Queued batch that was admitted before
            # a burst is handled by _admit's gate + preemption.)
            open_, why = self.trough_open()
            if not open_:
                self._count_shed(priority)
                self._m_requests.labels(outcome="429").inc()
                # short re-probe hint, NOT _retry_after(): the trough
                # reopens as soon as a slot frees (milliseconds), so
                # the congestion-derived >=1s interactive hint would
                # park the job manager past every trough worth filling
                raise EngineOverloaded(
                    f"batch trough closed: {why}",
                    float(root.common.serve.jobs.get(
                        "trough_retry_s", 0.05)))
        req = _Request(
            prompt, n_steps, float(temperature),
            None if top_k is None else int(top_k),
            None if top_p is None else float(top_p),
            None if eos_id is None else int(eos_id),
            np.asarray(jax.random.key_data(key)),
            time.monotonic() + (self.deadline_s if deadline_s is None
                                else float(deadline_s)),
            priority=priority, batch=batch)
        if pref is not None and pref.size:
            # the resume form IS the preemption harvest/resume state:
            # admission prefills prompt + prefix and decode continues
            # from the global position the interrupted run reached
            req.gen = pref
        req.stop_seqs = stop_seqs
        if stream:
            h = self._acquire_stream(req)
            req.stream = h
        if self.paged:
            # pool backpressure: when slots are free but the PAGES are
            # gone (long prompts at low slot occupancy), admission could
            # not happen anyway — answer the same 429/Retry-After as a
            # full queue instead of parking work a free slot cannot
            # serve.  A busy slot table falls through to the queue
            # check: pages drain as slots retire, so queued waiting is
            # the normal path there.  Prefix-cache hits are discounted
            # from the need: a request whose system prompt is already
            # resident only allocates its tail — the hot-shared-prefix
            # workload must not be the one spuriously rejected.
            # a resume submit sizes/hashes its EFFECTIVE prompt
            # (prompt + emitted prefix) — the same total span the
            # uninterrupted run held, with the prefix-covered pages
            # eligible for cache hits
            eff = req.effective_prompt()
            need = self._page_span(eff.size, req.end_index
                                   - int(eff.size) + 1)
            hashes = self._prefix_hashes(eff)
            req.page_hashes = hashes    # _reserve_pages reuses them
            with self._page_lock:
                need -= self._prefix_hits_locked(hashes, eff.size)
                avail = self.pages - int(
                    np.count_nonzero(self._page_ref))
            with self._qlock:
                free_slots = self.slots - int(self._active.sum())
                pool_bound = (need > avail
                              and free_slots > len(self._queue))
                if pool_bound and self.preempt and any(
                        r is not None and r.priority > priority
                        for r in self._slot_req):
                    # a strictly-lower-class slot is running: the
                    # scheduler may preempt it to free its pages, so
                    # queueing is the right answer, not a 429 (the read
                    # is advisory — a stale view only costs one queued
                    # wait bounded by the deadline)
                    pool_bound = False
            if pool_bound:
                with self._page_lock:
                    self._pool_rejected += 1
                self._count_shed(priority)
                self._m_requests.labels(outcome="429").inc()
                self._release_stream(req, "429")
                raise EngineOverloaded(
                    f"page pool exhausted ({avail} of {self.pages} "
                    f"pages free, request needs {need} beyond its "
                    "cached prefix)", self._retry_after())
        evicted = None
        with self._qlock:
            # admission decided under the lock; the 429 (which computes
            # Retry-After by re-taking the lock) raises outside it.
            # The controller's window (priority-scaled) bounds what the
            # hard queue_depth used to bound alone: under a sustained
            # SLO burn low classes shed first, then everyone.
            qlen = len(self._queue)
            # batch bypasses the AIMD window (the trough gate above is
            # its admission control) but never the hard queue depth;
            # it also cannot displace anyone — no class sits below it
            limit = (self.queue_depth if batch
                     else min(self.queue_depth,
                              self._admission.allowance(priority)))
            overloaded = qlen >= limit
            if overloaded:
                # full — hard depth or a burn-closed admission window —
                # a higher-class arrival may displace the youngest
                # queued request of a strictly lower class.  Without
                # this the window case would invert the priority
                # contract: a mid-class arrival 429s while
                # strictly-lower-class requests admitted just before
                # the window closed keep their spots.  Under ANY shed
                # the low classes go first, not whoever arrived later;
                # total queue length never grows (one out, one in).
                evicted = self._queue.steal_lower(priority)
                if evicted is not None:
                    self._queue.append(req)
                    overloaded = False
            if not overloaded and evicted is None:
                self._queue.append(req)
        if evicted is not None:
            retry = self._retry_after()
            self._count_shed(evicted.priority)
            # _observe_finish below lands the vt_requests_total 429
            evicted.finish(error=EngineOverloaded(
                "shed from a full queue by a higher-priority arrival",
                retry))
            self._observe_finish(evicted, "429")
        if overloaded:
            self._count_shed(priority)
            self._m_requests.labels(outcome="429").inc()
            self._release_stream(req, "429")
            raise EngineOverloaded(
                f"admission window full ({qlen} pending, window "
                f"{limit} for class {priority} of "
                f"{self.queue_depth} hard depth)", self._retry_after())
        self._wake.set()
        return req

    def generate(self, prompt, n_steps: int, *, temperature: float = 0.0,
                 top_k=None, top_p=None, eos_id=None, key=None,
                 timeout: Optional[float] = None, priority: int = 0,
                 batch: bool = False):
        """Blocking batch decode with the ``generate()`` contract:
        (B, P) int32 -> (B, P + n_steps) int32, rows past their eos
        padded with ``eos_id``.  Each row rides its own slot; row ``r``
        of a multi-row sampled request draws from ``fold_in(key, r)``
        (single-row requests use ``key`` itself, bitwise-matching
        ``generate()``).  ``priority`` is the request class
        (:meth:`submit`)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 2:
            raise ValueError("prompt must be (B, P)")
        B, P = prompt.shape
        if key is None:
            key = jax.random.key(0)
        reqs = []
        try:
            for r in range(B):
                rk = key if B == 1 else jax.random.fold_in(key, r)
                reqs.append(self.submit(
                    prompt[r], n_steps, temperature=temperature,
                    top_k=top_k, top_p=top_p, eos_id=eos_id, key=rk,
                    priority=priority, batch=batch))
            out = np.full((B, P + n_steps),
                          eos_id if eos_id is not None else 0, np.int32)
            for r, req in enumerate(reqs):
                if not req.done.wait(timeout):
                    raise TimeoutError("engine.generate timed out")
                if req.error is not None:
                    raise req.error
                out[r, :len(req.result)] = req.result
            return out
        except BaseException:
            # don't leak the batch's other rows: a mid-batch overflow
            # (or timeout) must not leave already-submitted rows
            # decoding to discarded results while the client retries —
            # expiring their deadline makes the scheduler drop queued
            # ones and retire in-flight ones on the next step
            for req in reqs:
                if not req.done.is_set():
                    req.deadline = 0.0
            raise

    def _pages_summary(self) -> Optional[dict]:
        """One consistent snapshot of the pool: refcounts, the prefix
        index AND the derived numbers under the same lock hold
        (used/cached and hit counters torn across a concurrent admission
        used to disagree — veles-tpu-lint VC201); None when dense."""
        if not self.paged:
            return None
        with self._page_lock:
            used = int(np.count_nonzero(self._page_ref))
            cached = sum(1 for pid in self._page_key
                         if self._page_ref[pid] == 0)
            hit = self._prefix_hit_pages
            miss = self._prefix_miss_pages
            evictions = self._evictions
            cow = self._cow_admissions
            pool_rejected = self._pool_rejected
        lookups = hit + miss
        return {
            "page_size": self.page_size, "pages": self.pages,
            "used": used, "cached": cached,
            "free": self.pages - used - cached,
            "tokens_resident": (used + cached) * self.page_size,
            "prefix_hit_pages": hit,
            "prefix_miss_pages": miss,
            "prefix_hit_rate": round(hit / lookups, 3) if lookups
            else 0.0,
            "prefix_tokens_reused": hit * self.page_size,
            "evictions": evictions,
            "cow_admissions": cow,
            "pool_rejected": pool_rejected,
        }

    def _goodput_summary(self) -> dict:
        """Decode goodput: achieved memory traffic per second against
        the configured HBM peak (model-bandwidth-utilization — the
        honesty check decode perf claims are scored by) and tokens/s
        normalized per local device."""
        ewma = self._step_wall_ewma
        # an idle engine streams nothing: freeze-free gauges report 0
        # once no decode OR verify step ran for a couple of seconds,
        # instead of showing the last load's bandwidth forever
        idle = (self._last_step_at <= 0
                or time.monotonic() - self._last_step_at > 2.0)
        bw = self._bw_ewma if self._bw_ewma > 0 and not idle else 0.0
        peak_gbps = resolve_peak_hbm_gbps()
        mbu = bw / (peak_gbps * 1e9) if peak_gbps > 0 else 0.0
        try:
            chips = max(jax.local_device_count(), 1)
        except Exception:
            chips = 1
        return {
            "decode_step_flops": self._decode_flops,
            "decode_step_bytes": self._decode_bytes,
            "decode_step_wall_ewma_s": round(ewma, 6),
            "decode_bandwidth_bytes_per_sec": round(bw, 1),
            "decode_mbu": round(mbu, 5),
            "tokens_per_sec_per_chip": round(
                self._tokens_per_sec / chips, 2),
            # windowed speculative accept rate next to the other
            # throughput-honesty numbers (0.0 when spec is off or idle)
            "spec_accept_rate": round(self._spec_accept_rate, 4),
        }

    def _headroom_slots(self, pages: Optional[dict]) -> int:
        """Max-length requests admissible right now: free slots, further
        bounded (paged) by how many max-length page spans the pool still
        holds — cached refcount-0 pages count as available because the
        allocator evicts them on demand."""
        free_slots = self.slots - int(self._active.sum())
        if pages is None:
            return max(free_slots, 0)
        avail = pages["free"] + pages["cached"]
        return max(min(free_slots, avail // max(self.n_ptab, 1)), 0)

    def trough_open(self) -> Tuple[bool, str]:
        """Batch-lane admission sensor (docs/serving.md "Batch lane"):
        batch work enters only while BOTH hold — interactive occupancy
        leaves at least ``serve.jobs.min_headroom_slots`` admissible
        slots (the vt_memory_headroom_slots signal) and the windowed
        SLO burn sits at or under ``serve.jobs.burn_ceiling`` (below
        the interactive controller's own shed threshold, so batch
        yields BEFORE interactive classes start paying).  Returns
        ``(open, reason)`` — the reason lands in the 429 body."""
        jobs_cfg = root.common.serve.jobs
        min_headroom = int(jobs_cfg.get("min_headroom_slots", 1))
        burn_ceiling = float(jobs_cfg.get("burn_ceiling", 1.0))
        headroom = self._headroom_slots(self._pages_summary())
        return self._trough_open_for(headroom, min_headroom,
                                     burn_ceiling)

    def _trough_open_for(self, headroom: int,
                         min_headroom: Optional[int] = None,
                         burn_ceiling: Optional[float] = None
                         ) -> Tuple[bool, str]:
        """The gate itself, on an already-computed headroom sample (the
        scheduler's _admit re-checks per tick without re-walking the
        pool)."""
        jobs_cfg = root.common.serve.jobs
        if min_headroom is None:
            min_headroom = int(jobs_cfg.get("min_headroom_slots", 1))
        if burn_ceiling is None:
            burn_ceiling = float(jobs_cfg.get("burn_ceiling", 1.0))
        if headroom < min_headroom:
            return False, (f"headroom {headroom} slots < "
                           f"serve.jobs.min_headroom_slots "
                           f"{min_headroom}")
        burn = self._admission.last_burn()
        if burn > burn_ceiling:
            return False, (f"SLO burn {burn:.2f} > "
                           f"serve.jobs.burn_ceiling {burn_ceiling}")
        return True, "ok"

    def _publish_gauges(self) -> dict:
        """Sample the point-in-time gauges (occupancy, queue depth,
        throughput, pool, goodput, memory headroom) into the registry
        and return the one consistent snapshot stats() renders.  Called
        by the scheduler's 0.5s status tick — a bare ``GET /metrics``
        scrape is never stale just because nothing polled ``/engine``
        — and from :meth:`stats`; NOT per decode step: the pool summary
        costs an O(pages) pass under ``_page_lock`` and scrape
        consumers read at ≥1s granularity anyway."""
        now = time.monotonic()
        mark_t, mark_n = self._rate_mark
        if now - mark_t >= 0.5:
            self._tokens_per_sec = ((self._tok_count.n - mark_n)
                                    / max(now - mark_t, 1e-9))
            self._rate_mark = (now, self._tok_count.n)
        b_t, b_n = self._batch_rate_mark
        if now - b_t >= 0.5:
            self._batch_tok_s = ((self._batch_tok_n - b_n)
                                 / max(now - b_t, 1e-9))
            self._batch_rate_mark = (now, self._batch_tok_n)
        s_t, s_prop, s_acc = self._spec_rate_mark
        if now - s_t >= 0.5:
            d_prop = self._spec_proposed.n - s_prop
            d_acc = self._spec_accepted.n - s_acc
            self._spec_accept_rate = d_acc / d_prop if d_prop else 0.0
            self._spec_rate_mark = (now, self._spec_proposed.n,
                                    self._spec_accepted.n)
        pages = self._pages_summary()
        with self._qlock:
            queue_depth = len(self._queue)
        occupancy = int(self._active.sum())
        good = self._goodput_summary()
        headroom = self._headroom_slots(pages)
        self._g_occupancy.set(occupancy)
        self._g_queue_depth.set(queue_depth)
        self._g_tokens_per_sec.set(self._tokens_per_sec)
        self._g_batch_tps.set(self._batch_tok_s)
        self._g_headroom.set(headroom)
        self._g_spec_accept_rate.set(self._spec_accept_rate)
        self._g_decode_bw.set(good["decode_bandwidth_bytes_per_sec"])
        self._g_decode_mbu.set(good["decode_mbu"])
        self._g_tps_chip.set(good["tokens_per_sec_per_chip"])
        if pages is not None:
            self._g_pages_used.set(pages["used"])
            self._g_pages_cached.set(pages["cached"])
            self._g_pages_free.set(pages["free"])
            self._g_prefix_hit_rate.set(pages["prefix_hit_rate"])
        return {"pages": pages, "queue_depth": queue_depth,
                "occupancy": occupancy, "goodput": good,
                "headroom_slots": headroom}

    def stats(self) -> dict:
        """JSON-able gauges for status pages / benches.  The counters
        are ScopedCounter views over the metrics registry, so the same
        increments back this dict, status.json, GET /engine and GET
        /metrics; the sampled gauges (occupancy / queue depth /
        throughput / goodput / headroom) are published to the registry
        here AND on the scheduler's 0.5s tick (:meth:`_publish_gauges`
        — one sample backs both the gauges and this dict)."""
        snap = self._publish_gauges()
        pages = snap["pages"]
        steps = max(self._decode_steps.n, 1)
        queue_depth = snap["queue_depth"]
        occupancy = snap["occupancy"]
        return {
            "slots": self.slots, "l_max": self.l_max,
            "paged": self.paged,
            **({"pages": pages} if pages is not None else {}),
            "occupancy": occupancy,
            "avg_occupancy": round(self._occupancy_sum / steps, 3),
            "queue_depth": queue_depth,
            "queue_limit": self.queue_depth,
            "tokens_per_sec": round(self._tokens_per_sec, 1),
            "tokens_generated": self._tok_count.n,
            "decode_steps": self._decode_steps.n,
            "dispatches": self._dispatches.n,
            "admitted": self._admitted.n, "retired": self._retired.n,
            "rejected": self._rejected.n, "timeouts": self._timeouts.n,
            "swaps": self._swaps, "draining": self._draining,
            "scheduler_crashed": self._died,
            # overload survival (docs/serving.md "Overload survival"):
            # the controller's live window, preemption volume, and shed
            # counts by request class
            "admission": {
                **self._admission.state(),
                "priorities": self.priorities,
                "preempt": self.preempt,
                "prefill_chunk": self.prefill_chunk,
                "preemptions": self._preempted.n,
                "shed_by_class": self._shed_snapshot(),
            },
            "compile": self.step_cache.stats(),
            **({"spec": {
                "k": self.spec_k, "drafter": self.spec_drafter,
                "proposed": self._spec_proposed.n,
                "accepted": self._spec_accepted.n,
                "verify_steps": self._verify_steps,
                "accept_rate": round(
                    self._spec_accepted.n
                    / max(self._spec_proposed.n, 1), 4),
            }} if self.spec else {}),
            **({"megastep": {
                "n": self.megastep,
                "mega_dispatches": self._mega_steps,
            }} if self.megastep > 1 else {}),
            **({"kv_transfer": kvt}
               if (kvt := self._kv_transfer_summary()) is not None
               else {}),
            # batch lane (docs/serving.md "Batch lane"): whether the
            # trough gate would admit right now, and the throughput
            # the SLO histograms deliberately never see
            "batch": {
                "trough_open": self._trough_open_for(
                    snap["headroom_slots"])[0],
                "tokens_generated": self._batch_tok_n,
                "tokens_per_sec": round(self._batch_tok_s, 1),
                "preemptions": self._batch_preempted.n,
            },
            "goodput": snap["goodput"],
            "memory": {
                "headroom_slots": snap["headroom_slots"],
                **self._mem_bytes,          # THIS engine's bytes, not
            },                              # the process ledger's
        }

    def _count_shed(self, priority: int):
        """One shed, every ledger in lockstep: the engine's rejected
        counter, the per-class stats snapshot, and the
        ``vt_shed_total`` series — the three paths that shed (pool
        429, admission-window 429, hard-full displacement) must never
        drift apart on these.  ``vt_requests_total{outcome="429"}`` is
        deliberately NOT counted here: displacement routes it through
        ``_observe_finish`` (the request finishes), the raise paths
        count it at the raise site (no request object ever finishes).
        Takes ``_qlock`` — call outside it."""
        self._rejected.inc()
        self._m_shed.labels(priority=str(priority)).inc()
        with self._qlock:
            self._shed_by_class[priority] = \
                self._shed_by_class.get(priority, 0) + 1

    def _shed_snapshot(self) -> dict:
        """Per-class shed counts as a JSON-able dict (one consistent
        copy under the queue lock)."""
        with self._qlock:
            return {str(k): v
                    for k, v in sorted(self._shed_by_class.items())}

    # -- scheduler ----------------------------------------------------------
    def _retry_after(self) -> float:
        """429 Retry-After estimate, derived from actual congestion so
        clients back off proportionally (the honest-shedding half of
        the overload contract): queued decode work over recent
        throughput, floored by the queue-wait EWMA current admissions
        are really paying, scaled by how far the admission controller
        has closed the window (a half-closed window doubles the hint).
        Bounded to [1, 60] seconds.  Takes the queue lock itself —
        callers raise their 429 AFTER releasing it (iterating the
        queue while submit threads append was a mutation-during-
        iteration crash waiting for load; veles-tpu-lint VC201)."""
        with self._qlock:
            queued = sum(r.n_steps for r in self._queue) or 1
        rate = max(self._tokens_per_sec, 1.0)
        est = max(queued / rate, self._qwait_ewma)
        est *= self._admission.backoff_factor()
        return min(60.0, max(1.0, est))

    def _loop(self):
        from . import faults
        try:
            while not self._stop_evt.is_set():
                self._maybe_report()
                if faults.enabled():
                    plan = faults.get_plan()
                    if plan.admission_burst \
                            and faults.fire_once("admission_burst"):
                        # synthetic queue flood (runtime/faults.py):
                        # the controller-shed rehearsal's backlog
                        self._inject_burst(int(plan.admission_burst))
                    # lint: disable=VC201 bool(deque) is atomic under
                    # the GIL; a stale wakeup read only costs one 50ms
                    # tick
                    if (self._queue or self._active.any()) \
                            and plan.scheduler_crash \
                            and faults.fire_once("scheduler_crash"):
                        # injected crash point (tests/test_faults.py):
                        # fire only with work pending so the crash
                        # exercises the fail-all path, once per arming
                        raise faults.FaultInjected(
                            "injected decode-scheduler crash")
                # decode-step boundary: no program is running right now,
                # so a staged weight swap flips here atomically — and
                # staged KV-page imports land on the same boundary (the
                # scheduler thread owns every _caches write)
                self._apply_swap()
                self._apply_kv_imports()
                # lint: disable=VC201 bool(deque) is atomic under the
                # GIL; a stale wakeup read only costs one 50ms tick
                if not self._active.any() and not self._queue \
                        and not self._chunking:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                    continue
                if not self._active.any() and not self._chunking \
                        and self.window_s > 0:
                    # batching window: concurrent arrivals get admitted
                    # together and share the first decode steps instead
                    # of the first request racing its slot ahead
                    time.sleep(self.window_s)
                self._expire_queue()
                self._admit()  # mid-flight too: no drain barrier
                # chunked prefill: ONE bounded slice per mid-prefill
                # slot per iteration, so a long prompt and the decode
                # step below take turns instead of the prompt
                # monopolizing the scheduler for its whole length
                self._advance_prefills()
                if self._active.any():
                    self._advance_once()
                self._maybe_report()
        except Exception as e:  # noqa: BLE001 — a dead scheduler must
            # fail pending work loudly, not hang every client forever
            self._died = True
            self.exception("decode engine scheduler died")
            if self.status is not None:
                try:
                    self.status.record_event(
                        "scheduler_crash",
                        error=f"{type(e).__name__}: {e}")
                except Exception:  # status must never mask the crash
                    pass
            # queued AND mid-flight requests all fail with the same
            # clearly-typed error (HTTP 500 in restful.py, not the 503
            # a drain answers) naming the original exception
            self._fail_all(SchedulerCrashed(
                f"engine scheduler crashed: {type(e).__name__}: {e}"))
        finally:
            # a swap staged during shutdown still flips (harmless) so
            # its waiter is released instead of blocking to timeout;
            # staged KV imports drain for the same reason
            self._apply_swap()
            self._apply_kv_imports()
            self._fail_all(EngineStopped("engine stopped"))

    def _fail_all(self, err: Exception):
        outcome = "crash" if isinstance(err, SchedulerCrashed) \
            else "stopped"
        with self._qlock:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            req.finish(error=err)
            self._observe_finish(req, outcome)
        for s, req in enumerate(self._slot_req):
            if req is not None:
                req.finish(error=err)
                self._slot_req[s] = None
                self._observe_finish(req, outcome)
            self._release_slot_pages(s)
        self._chunking.clear()
        self._active[:] = False

    def _expire_queue(self):
        """Fail queued requests whose deadline passed while they waited
        behind a full slot set (they'd otherwise only be checked when a
        slot freed)."""
        now = time.monotonic()
        expired = []
        with self._qlock:
            if self._queue and any(now > r.deadline
                                   for r in self._queue):
                expired = self._queue.remove_if(
                    lambda r: now > r.deadline)
        for r in expired:
            self._timeouts.inc()
            r.finish(error=TimeoutError(
                "request deadline expired while queued"))
            self._observe_finish(r, "504")

    def _free_slot(self) -> Optional[int]:
        """A slot that is neither decoding nor mid-(chunked-)prefill —
        ``_slot_req`` is the occupancy truth; ``_active`` alone would
        hand a chunking slot to a second request."""
        for s in range(self.slots):
            if not self._active[s] and self._slot_req[s] is None:
                return s
        return None

    def _pick_victim(self, priority: int) -> Optional[int]:
        """Preemption victim for an arrival of class ``priority``: the
        occupied slot of the LOWEST class strictly below it (largest
        class index), youngest run among ties (latest admission — the
        one losing the least progress).  None when preemption is off or
        nothing strictly lower is running."""
        if not self.preempt:
            return None
        best, best_key = None, None
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None or req.priority <= priority:
                continue
            k = (req.priority, req.run_started_at or 0.0)
            if best is None or k > best_key:
                best, best_key = s, k
        return best

    def _preempt_can_free(self, req) -> bool:
        """Upper-bound feasibility of preempting for pages: could
        evicting EVERY strictly-lower-class slot possibly free enough
        pages for ``req``?  A victim's distinct mapped pages bound
        what its release can return (shared-prefix pages stay
        referenced elsewhere), so False means preemption can never
        satisfy the need — requeue instead of futilely mass-evicting
        victims that each lose their progress.  Scheduler thread only
        (``_ptab``/``_slot_req`` are its state)."""
        eff = req.effective_prompt()
        P = int(eff.size)
        need = self._page_span(P, req.end_index - P + 1)
        hashes = req.page_hashes or self._prefix_hashes(eff)
        reclaimable = set()
        for s in range(self.slots):
            r = self._slot_req[s]
            if r is not None and r.priority > req.priority:
                reclaimable.update(
                    int(p) for p in np.unique(self._ptab[s])
                    if p != self._scratch)
        with self._page_lock:
            need -= self._prefix_hits_locked(hashes, P)
            avail = self.pages - int(np.count_nonzero(self._page_ref))
        return need <= avail + len(reclaimable)

    def _preempt(self, slot: int):
        """Retire-and-requeue the slot so a higher-priority request can
        take its place: harvest the tokens generated so far into
        ``req.gen`` (a later resume re-prefills prompt + gen, so the
        final stream is bitwise an uninterrupted run — the prefill's
        sampling-key folds are global-position), release the refcounted
        KV pages, and put the victim back at the FRONT of its own
        class.  Scheduler thread only."""
        req = self._slot_req[slot]
        if self._active[slot]:
            eff_len = int(req.prompt.size) + int(req.gen.size)
            pos = int(self._pos[slot])
            fresh = np.asarray(self._toks[slot, eff_len:pos + 1],
                               np.int32)
            if fresh.size:
                req.gen = np.concatenate([req.gen, fresh])
        self._active[slot] = False
        self._chunking.discard(slot)
        self._slot_req[slot] = None
        self._release_slot_pages(slot)
        req.slot = None
        req.page_row = None
        req.prefix_start = 0
        req.page_hashes = ()
        req.chunk_next = 0
        req._eff = None                 # prompt grew by the harvest
        req.preemptions += 1
        self._preempted.inc()
        if req.batch:
            # the batch lane yielding to interactive traffic — the
            # instant-yield half of the trough-filler contract
            self._batch_preempted.inc()
        with self._qlock:
            self._queue.appendleft(req)

    def _admit(self) -> int:
        """Move queued requests into free slots (prefill); returns the
        number admitted.  Runs on the scheduler thread only.  When the
        head of the queue outranks a running slot and no capacity is
        free — slots, or pages under the paged layout — the scheduler
        may preempt (docs/serving.md "Overload survival")."""
        n = 0
        while True:
            with self._qlock:
                req = self._queue.popleft()
            if req is None:
                return n
            now = time.monotonic()
            if now > req.deadline:
                self._timeouts.inc()
                req.finish(error=TimeoutError(
                    "request deadline expired while queued"))
                self._observe_finish(req, "504")
                continue
            if req.batch:
                # trough gate, re-checked at admission time: batch that
                # queued during a lull must keep waiting when a burst
                # arrived in between.  Batch is the LOWEST class, so
                # popleft only surfaces it once no interactive request
                # is queued — requeue-at-front and stop admitting.
                open_, _why = self.trough_open()
                if not open_:
                    with self._qlock:
                        self._queue.appendleft(req)
                    return n
            slot = self._free_slot()
            if slot is None:
                victim = self._pick_victim(req.priority)
                if victim is None or (
                        self.paged and not self._preempt_can_free(req)):
                    # no capacity and nothing preemptible — or, on the
                    # paged layout, preemption could free the SLOT but
                    # provably never enough PAGES (the same feasibility
                    # bound the page-reservation loop below applies): a
                    # victim evicted here would lose its progress to a
                    # full re-prefill for an admission that still
                    # cannot happen.  Requeue at the FRONT of its class
                    # and stop admitting.
                    with self._qlock:
                        self._queue.appendleft(req)
                    return n
                self._preempt(victim)
                slot = self._free_slot()
            ok = True
            while self.paged and not self._reserve_pages(req):
                # the pool cannot host it right now: preempt a strictly
                # lower class to free its pages, else requeue — pages
                # free as slots retire, deadlines bound the wait.  But
                # only preempt when preemption can plausibly SATISFY
                # the need: mass-evicting every lower slot (each losing
                # its progress to a full re-prefill) for a request the
                # pool still cannot host would be pure waste
                victim = self._pick_victim(req.priority)
                if victim is None or not self._preempt_can_free(req):
                    with self._qlock:
                        self._queue.appendleft(req)
                    ok = False
                    break
                self._preempt(victim)
            if not ok:
                return n
            self._prefill(int(slot), req)
            n += 1

    def _inject_burst(self, n: int):
        """``faults.admission_burst``: append ``n`` synthetic minimal
        lowest-class requests straight to the queue — deliberately
        bypassing submit()'s shed gate, because the rehearsal is "the
        backlog already exists; prove the controller sheds and
        re-opens" (tests/test_chaos.py).  Nobody waits on their done
        events; they decode and retire like any request."""
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        for _ in range(int(n)):
            r = _Request(np.asarray([0], np.int32), 2, 0.0, None, None,
                         None, kd, time.monotonic() + self.deadline_s,
                         priority=self.priorities - 1)
            with self._qlock:
                self._queue.append(r)

    # -- page pool (scheduler thread owns mutation; _page_lock guards the
    # cross-thread reads in submit() and stats()) ---------------------------
    def _touch(self, pid: int):  # requires-lock: self._page_lock
        self._tick += 1
        self._page_tick[pid] = self._tick

    def _page_span(self, P: int, n_steps: int) -> int:
        """Worst-case pages a request can ever reference: KV lands at
        positions ``0 .. P + n_steps - 2`` (the final sampled token is
        emitted but its KV is never computed — the slot retires at
        ``end = P + n_steps - 1``), so the span is one cell SHORT of
        the token count; counting the full count would strand a page
        per request whenever the true span is page-aligned."""
        return -(-(P + n_steps - 1) // self.page_size)

    def _prefix_hashes(self, prompt):
        """Chained content hashes of the prompt's FULL pages
        (:func:`prefix_page_hashes` — shared with the fleet router's
        affinity dispatch so both sides key the same bytes)."""
        if not self._prefix_ok:
            return []
        return prefix_page_hashes(prompt, self.page_size)

    def _prefix_hits_locked(self, hashes, P: int) -> int:  # requires-lock: self._page_lock
        """Leading pages already in the prefix index (caller holds
        ``_page_lock``), capped so at least the LAST prompt token is
        recomputed: the first sampled token needs its logits, and a
        fully-shared prompt would otherwise have nothing to run."""
        hits = 0
        for h in hashes:
            if h not in self._prefix_index:
                break
            hits += 1
        while hits and hits * self.page_size > P - 1:
            hits -= 1
        return hits

    def _reserve_pages(self, req) -> bool:
        """Map the request onto the pool: chained-hash prefix lookup over
        its full prompt pages (hits map shared read-only pages,
        refcount++), fresh pages for the rest of its worst-case span.
        On success ``req.page_row`` / ``req.prefix_start`` /
        ``req.page_hashes`` are set; on shortage every side effect is
        rolled back and False is returned (the caller requeues).  A
        preemption resume reserves for its EFFECTIVE prompt (original +
        generated-so-far) and the steps still owed — the same total
        span the uninterrupted run held."""
        psz = self.page_size
        eff = req.effective_prompt()
        P = int(eff.size)
        need = self._page_span(P, req.end_index - P + 1)
        full = P // psz                          # whole-prompt pages
        # submit() already hashed the prompt; () is also legitimate
        # (short prompt / prefix reuse off / a preemption resume, whose
        # effective prompt grew) and free to recompute
        hashes = req.page_hashes or self._prefix_hashes(eff)
        with self._page_lock:
            hits = self._prefix_hits_locked(hashes, P)
            row = np.full(self.n_ptab, self._scratch, np.int32)
            taken = []
            remote = 0
            for i in range(hits):
                pid = self._prefix_index[hashes[i]]
                self._page_ref[pid] += 1
                self._touch(pid)
                row[i] = pid
                taken.append(pid)
                if pid in self._imported_pages:
                    remote += 1
            for i in range(hits, need):
                pid = self._alloc_page_locked()
                if pid is None:          # shortage: roll back, requeue
                    for p in taken:
                        self._page_ref[p] -= 1
                        if self._page_ref[p] <= 0:
                            self._page_ref[p] = 0
                            if p not in self._page_key:
                                self._page_free.append(p)
                    return False
                row[i] = pid
                taken.append(pid)
            self._prefix_hit_pages += hits
            self._prefix_miss_pages += max(full - hits, 0)
            if remote:
                # the hit landed on pages a peer prefilled and shipped
                # over (import_pages) — the fleet-wide prefix-sharing
                # payoff signal (vt_prefix_remote_hits_total)
                self._remote_hit_pages += remote
                self._m_remote_hits.inc(remote)
            if hits:
                # copy-on-write admission: a shared prefix was mapped
                # read-only and the first divergent token onward is
                # recomputed into private pages
                self._cow_admissions += 1
        req.page_row = row
        req.prefix_start = hits * psz
        req.page_hashes = hashes
        return True

    def _alloc_page_locked(self):  # requires-lock: self._page_lock
        """One free page, evicting the least-recently-used CACHED page
        (refcount 0 but still registered in the prefix index) when the
        free list is empty; None when the pool is truly exhausted."""
        if self._page_free:
            pid = self._page_free.pop()
            self._page_ref[pid] = 1
            self._imported_pages.discard(pid)
            self._touch(pid)
            return pid
        best, best_tick = None, None
        for pid in self._page_key:
            if self._page_ref[pid] == 0 and (
                    best is None or self._page_tick[pid] < best_tick):
                best, best_tick = pid, self._page_tick[pid]
        if best is None:
            return None
        del self._prefix_index[self._page_key.pop(best)]
        self._evictions += 1
        self._page_ref[best] = 1
        self._imported_pages.discard(best)
        self._touch(best)
        return best

    def _register_prefix_pages(self, req):
        """After a prefill: publish the request's freshly computed FULL
        prompt pages in the prefix index so the next request sharing the
        prefix prefills only its tail.  Pages holding the prompt's
        partial tail or generated tokens stay private (their content is
        not a pure function of a whole-page prompt prefix)."""
        psz = self.page_size
        full = int(req.effective_prompt().size) // psz
        hits = req.prefix_start // psz
        with self._page_lock:
            for i in range(hits, min(full, len(req.page_hashes))):
                h = req.page_hashes[i]
                pid = int(req.page_row[i])
                if h not in self._prefix_index:
                    self._prefix_index[h] = pid
                    self._page_key[pid] = h
                self._touch(pid)

    def _release_slot_pages(self, slot: int):
        """Drop the slot's references; refcount-0 pages return to the
        free list unless the prefix index still caches them (a cached
        page stays resident, serving future prefix hits, until LRU
        eviction reclaims it)."""
        if not self.paged:
            return
        with self._page_lock:
            for pid in self._ptab[slot]:
                pid = int(pid)
                if pid == self._scratch:
                    continue
                self._page_ref[pid] -= 1
                if self._page_ref[pid] <= 0:
                    self._page_ref[pid] = 0
                    if pid not in self._page_key:
                        self._page_free.append(pid)
            self._ptab[slot] = self._scratch

    # -- KV-page transfer: serialized prefix-page export/import across
    # replicas (docs/serving.md "Disaggregated prefill/decode").  The
    # wire format is magic + length-prefixed JSON header (page_size,
    # weights version, per-entry dtype/shape layout, per-page integrity
    # sha256) + concatenated raw page rows; pages are keyed by the same
    # chained content hashes the prefix index uses, so an imported page
    # is bitwise the page a local prefill would have computed. ---------

    def _require_transfer(self):
        """KV-page transfer needs content-addressed pages: dense caches
        and recurrent chains reject LOUDLY (the REST layer's 400) —
        shipping rows whose content is not a pure function of a prompt
        prefix would silently corrupt the importer's decode."""
        if not self.paged:
            raise ValueError(
                "KV-page transfer requires the paged KV layout "
                "(serve.paged=True); dense caches have no "
                "content-addressed pages to ship")
        if not self._prefix_ok:
            raise ValueError(
                "KV-page transfer requires prefix reuse, which "
                "recurrent units disable (their cache content is not a "
                "pure function of a whole-page prompt prefix)")

    @property
    def kv_wver(self) -> str:
        """Weights-version token stamped into every exported blob: the
        parameter-tree signature hash joined with the hot-swap counter.
        Import refuses a mismatch — pages computed under other weights
        must never enter the prefix index (the same staleness rule that
        makes :meth:`_apply_swap` invalidate the local cache)."""
        return f"{self._kv_sig}.{self._swaps}"

    def _kv_xfer_entries(self) -> list:
        """Per-entry wire layout ``(name, part, dtype, row_shape)`` over
        the attention caches, in deterministic order — the header both
        sides must agree on byte for byte."""
        if self._kv_entry_cache is None:
            ents = []
            for name in sorted(self._attn_cache_keys()):
                for part in ("k", "v"):
                    arr = self._caches[name][part]
                    ents.append((name, part, str(np.dtype(arr.dtype)),
                                 tuple(int(d) for d in arr.shape[1:])))
            self._kv_entry_cache = ents
        return self._kv_entry_cache

    def _kv_page_bytes(self) -> int:
        """Wire payload bytes of ONE page (all cache entries)."""
        return sum(int(np.dtype(dt).itemsize) * int(np.prod(shape))
                   for _n, _p, dt, shape in self._kv_xfer_entries())

    @staticmethod
    def _norm_hash(h) -> bytes:
        """Page hashes are raw sha256 digests internally; the wire and
        query-string forms are hex."""
        return bytes.fromhex(h) if isinstance(h, str) else bytes(h)

    def hot_page_hashes(self, k: int) -> list:
        """The K hottest cached prefix pages (refcount desc, then LRU
        recency) as raw digests — the rolling drain's pre-warm set.
        Pages ship independently, so a truncated chain still serves
        hits up to its first missing page."""
        self._require_transfer()
        with self._page_lock:
            ranked = sorted(
                self._page_key.items(),
                key=lambda it: (int(self._page_ref[it[0]]),
                                int(self._page_tick[it[0]])),
                reverse=True)
            return [h for _pid, h in ranked[:max(int(k), 0)]]

    def export_pages(self, prefix_hashes) -> bytes:
        """Serialize the requested prefix pages (those present; unknown
        hashes are silently omitted) into the transfer wire format.
        Requested pages are pinned (refcount++) for the gather so
        eviction cannot recycle a row mid-read — registered pages are
        written only by their original prefill, so the pinned rows are
        immutable."""
        self._require_transfer()
        t0 = time.monotonic()
        pinned = []
        with self._page_lock:
            seen = set()
            for h in prefix_hashes:
                h = self._norm_hash(h)
                pid = self._prefix_index.get(h)
                if pid is None or h in seen:
                    continue
                seen.add(h)
                self._page_ref[pid] += 1
                self._touch(pid)
                pinned.append((h, pid))
        try:
            entries = self._kv_xfer_entries()
            caches = self._caches
            rows = []
            if pinned:
                pids = np.asarray([pid for _h, pid in pinned], np.int32)
                rows = [np.asarray(caches[name][part][pids])
                        for name, part, _dt, _shape in entries]
            pages = []
            payload = bytearray()
            for i, (h, _pid) in enumerate(pinned):
                page = b"".join(np.ascontiguousarray(r[i]).tobytes()
                                for r in rows)
                pages.append({"hash": h.hex(),
                              "sha256": hashlib.sha256(page).hexdigest()})
                payload += page
        finally:
            # unpin: same discipline as _release_slot_pages — a page a
            # concurrent swap unregistered while we held it goes back
            # to the free list here
            with self._page_lock:
                for _h, pid in pinned:
                    self._page_ref[pid] -= 1
                    if self._page_ref[pid] <= 0:
                        self._page_ref[pid] = 0
                        if pid not in self._page_key:
                            self._page_free.append(pid)
        hdr = json.dumps({
            "page_size": self.page_size, "wver": self.kv_wver,
            "entries": [[n, p, dt, list(s)] for n, p, dt, s in entries],
            "pages": pages,
        }).encode()
        blob = _KV_MAGIC + len(hdr).to_bytes(4, "little") + hdr \
            + bytes(payload)
        with self._page_lock:
            self._kv_exported_pages += len(pinned)
            self._kv_export_bytes += len(blob)
        self._m_kv_exported.inc(len(pinned))
        self._m_kv_bytes.labels(direction="out").inc(len(blob))
        self._m_kv_seconds.labels(direction="out").observe(
            time.monotonic() - t0)
        return blob

    def _decode_pages_blob(self, blob) -> list:
        """Validate a wire blob against the LOCAL geometry and weights
        version; returns ``[(hash, [row arrays in entry order]), ...]``.
        Every defect is a loud ValueError (the REST layer's 400) — a
        page of someone else's KV silently entering the prefix index
        would break bitwise identity for every request hitting it."""
        blob = bytes(blob)
        if blob[:len(_KV_MAGIC)] != _KV_MAGIC:
            raise ValueError("not a KV-page blob (bad magic)")
        off = len(_KV_MAGIC)
        if len(blob) < off + 4:
            raise ValueError("truncated KV-page blob (no header)")
        n = int.from_bytes(blob[off:off + 4], "little")
        off += 4
        try:
            hdr = json.loads(blob[off:off + n].decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise ValueError(f"corrupt KV-page header: {e}") from e
        off += n
        if int(hdr.get("page_size", -1)) != self.page_size:
            raise ValueError(
                f"page_size mismatch: blob {hdr.get('page_size')} vs "
                f"local {self.page_size}")
        if str(hdr.get("wver")) != self.kv_wver:
            raise ValueError(
                f"weights-version mismatch: blob {hdr.get('wver')!r} "
                f"vs local {self.kv_wver!r} — pages computed under "
                "other weights cannot serve here")
        local = [[n_, p, dt, list(s)]
                 for n_, p, dt, s in self._kv_xfer_entries()]
        if hdr.get("entries") != local:
            raise ValueError(
                "cache-entry layout mismatch (names/dtypes/shapes "
                "differ from the local paged caches)")
        sizes = [(np.dtype(dt), tuple(s),
                  int(np.dtype(dt).itemsize) * int(np.prod(s)))
                 for _n, _p, dt, s in self._kv_xfer_entries()]
        page_bytes = sum(sz for _dt, _s, sz in sizes)
        pages_hdr = hdr.get("pages") or []
        if len(blob) - off != page_bytes * len(pages_hdr):
            raise ValueError(
                f"payload size mismatch: {len(blob) - off} bytes for "
                f"{len(pages_hdr)} pages of {page_bytes}")
        out = []
        for meta in pages_hdr:
            page = blob[off:off + page_bytes]
            off += page_bytes
            if hashlib.sha256(page).hexdigest() != meta.get("sha256"):
                raise ValueError(
                    "page integrity check failed for "
                    f"{meta.get('hash')!r}")
            rows, p_off = [], 0
            for dt, shape, sz in sizes:
                rows.append(np.frombuffer(
                    page[p_off:p_off + sz], dtype=dt).reshape(shape))
                p_off += sz
            out.append((self._norm_hash(str(meta.get("hash"))), rows))
        return out

    def import_pages(self, blob, *, timeout: float = 30.0) -> dict:
        """Deserialize a peer's prefix pages into the local pool.
        Validation (geometry, weights version, per-page integrity) is
        all-or-nothing and raises ValueError; the APPLY is per-page
        best-effort: already-resident hashes are skipped, and when the
        pool is fully referenced the page is dropped rather than the
        transfer failed.  The device writes land on the scheduler
        thread at a decode-step boundary (the swap discipline), so this
        blocks until the next tick applies them.  Imported pages enter
        the prefix index refcount-0 — cached, evictable, and dropped by
        a swap's invalidation exactly like locally-prefilled ones."""
        self._require_transfer()
        t0 = time.monotonic()
        pages = self._decode_pages_blob(blob)
        box = {"applied": None, "error": None}
        done = threading.Event()
        with self._kv_import_lock:
            self._kv_imports.append((pages, box, done))
        if self.started:
            self._wake.set()
        else:
            self._apply_kv_imports()
        deadline = time.monotonic() + float(timeout)
        while not done.wait(0.05):
            if not self.started:
                # the scheduler stopped between the enqueue and its
                # drain: apply inline (the deque pop under
                # _kv_import_lock makes concurrent drains safe)
                self._apply_kv_imports()
            elif time.monotonic() > deadline:
                raise TimeoutError(
                    f"KV-page import not applied within {timeout}s "
                    "(scheduler wedged?)")
        if box["error"]:
            raise ValueError(
                f"KV-page import failed mid-apply: {box['error']}")
        imported, skipped, dropped, hashes = box["applied"]
        with self._page_lock:
            self._kv_imported_pages += imported
            self._kv_import_bytes += len(blob)
        self._m_kv_imported.inc(imported)
        self._m_kv_bytes.labels(direction="in").inc(len(blob))
        self._m_kv_seconds.labels(direction="in").observe(
            time.monotonic() - t0)
        return {"imported": imported, "skipped": skipped,
                "dropped": dropped,
                "hashes": [h.hex() for h in hashes]}

    def _claim_import_page(self):
        """One pool page claimed (``self._page_ref`` goes 1) for an
        in-flight KV-page import; None when every page is referenced
        by a live slot.  The "kv-transfer" acquire (analysis registry
        RESOURCE_PAIRS): every exit must reach
        :meth:`_abort_import_page` or hand the page to
        :meth:`_register_import_page`."""
        with self._page_lock:
            return self._alloc_page_locked()

    def _abort_import_page(self, pid: int):
        """Return a claimed-but-unregistered import page to
        ``self._page_free`` (the "kv-transfer" release): the apply
        aborted and the page never entered the prefix index."""
        with self._page_lock:
            self._page_ref[pid] = 0
            self._page_free.append(pid)

    def _register_import_page(self, pid: int, h: bytes):
        """Publish an imported page in the prefix index exactly like a
        locally-prefilled one: refcount back to 0 (cached state —
        evictable under pressure, freed by release-path bookkeeping
        once unregistered) plus the imported-page attribution set."""
        with self._page_lock:
            self._page_ref[pid] = 0
            self._prefix_index[h] = pid
            self._page_key[pid] = h
            self._imported_pages.add(pid)
            self._touch(pid)

    def _apply_kv_imports(self):
        """Drain staged KV-page imports (scheduler thread at a decode-
        step boundary, or inline on a stopped engine).  Per page:
        skip duplicates, claim a pool page, write the device rows,
        register.  A write failure releases the claimed page and fails
        THAT import's caller — never the scheduler every other request
        shares."""
        while True:
            with self._kv_import_lock:
                if not self._kv_imports:
                    return
                pages, box, done = self._kv_imports.popleft()
            imported = skipped = dropped = 0
            hashes = []
            entries = self._kv_xfer_entries()
            try:
                for h, rows in pages:
                    with self._page_lock:
                        pid0 = self._prefix_index.get(h)
                        if pid0 is not None:
                            self._touch(pid0)
                    if pid0 is not None:
                        skipped += 1
                        hashes.append(h)
                        continue
                    pid = self._claim_import_page()
                    if pid is None:
                        # every page is referenced by a live slot:
                        # drop this page rather than fail the
                        # transfer — the peer's prefix simply stays
                        # cold here
                        dropped += 1
                        continue
                    try:
                        for (name, part, _d, _s), row in zip(entries,
                                                             rows):
                            self._caches[name][part] = \
                                self._caches[name][part].at[pid].set(row)
                    except Exception:
                        self._abort_import_page(pid)
                        raise
                    self._register_import_page(pid, h)
                    imported += 1
                    hashes.append(h)
            except Exception as e:  # noqa: BLE001 — surface on the
                # importer's call, never crash the shared scheduler
                box["error"] = f"{type(e).__name__}: {e}"
            box["applied"] = (imported, skipped, dropped, hashes)
            done.set()

    def _kv_transfer_summary(self) -> Optional[dict]:
        """The ``stats()["kv_transfer"]`` group: transfer volume, the
        remote-hit attribution, and the two numbers the fleet router's
        fetch-payoff policy scrapes (wire bytes per page and the
        prefill-throughput EWMA)."""
        if not self.paged:
            return None
        with self._page_lock:
            out = {
                "exported_pages": self._kv_exported_pages,
                "imported_pages": self._kv_imported_pages,
                "export_bytes": self._kv_export_bytes,
                "import_bytes": self._kv_import_bytes,
                "remote_hit_pages": self._remote_hit_pages,
            }
        out["page_bytes"] = self._kv_page_bytes() if self._prefix_ok \
            else 0
        out["prefill_tok_s"] = round(self._prefill_tok_s, 1)
        out["wver"] = self.kv_wver
        return out

    def _prefill(self, slot: int, req: _Request):
        """Admit ``req`` into ``slot``.  Short tails prefill in one
        program call; a tail longer than ``prefill_chunk`` instead
        REGISTERS the slot for chunked prefill — one bounded slice per
        scheduler iteration, interleaved with decode steps — so a long
        prompt costs everyone bounded latency instead of a monopolized
        scheduler (docs/serving.md "Overload survival")."""
        # reserve the slot BEFORE the device program runs: between the
        # queue pop and _active[slot] going true the request must stay
        # visible to drain()'s idleness check (and to _fail_all)
        self._slot_req[slot] = req
        req.slot = slot
        now = time.monotonic()
        req.run_started_at = now
        if req.admitted_at is None:
            # first admission only: a preemption resume is not a fresh
            # queue wait (its wait was already observed once)
            req.admitted_at = now
            wait = now - req.submitted_at
            if not req.batch:
                # batch never lands in the SLO histograms (the tracker
                # snapshots whole registry histograms, so exclusion
                # must happen here) nor in the Retry-After EWMA — a
                # deliberately-parked bulk prompt would poison both
                self._m_queue_wait.observe(wait)
                self._qwait_ewma = wait if self._qwait_ewma <= 0 \
                    else 0.9 * self._qwait_ewma + 0.1 * wait
            self._admitted.inc()
        eff = req.effective_prompt()
        P = int(eff.size)
        # the bucket is sized by the UN-SHARED tail: a prefix-cache hit
        # turns a long prompt into a short prefill
        start = req.prefix_start if self.paged else 0
        req.chunk_first = start
        if self.paged:
            self._ptab[slot] = req.page_row
        if self.prefill_chunk > 0 and self._chunk_capable \
                and P - start > self.prefill_chunk:
            req.chunk_next = start
            self._chunking.add(slot)
            return
        self._prefill_call(slot, req, eff, start, P - start, last=True)

    def _advance_prefills(self):
        """One chunk slice per mid-prefill slot (scheduler thread):
        the long-prompt/decode interleave, plus the mid-prefill
        deadline sweep (a chunking slot is neither queued nor active,
        so neither other sweep would ever fail it)."""
        for slot in sorted(self._chunking):
            req = self._slot_req[slot]
            if req is None:             # defensive: state went away
                self._chunking.discard(slot)
                continue
            if time.monotonic() > req.deadline:
                self._chunking.discard(slot)
                self._slot_req[slot] = None
                self._release_slot_pages(slot)
                self._timeouts.inc()
                req.finish(error=TimeoutError(
                    "request deadline expired mid-prefill"))
                self._observe_finish(req, "504")
                continue
            eff = req.effective_prompt()
            P = int(eff.size)
            cur = req.chunk_next
            n = min(self.prefill_chunk, P - cur)
            last = cur + n >= P
            self._prefill_call(slot, req, eff, cur, n, last=last)
            req.chunk_next = cur + n
            if last:
                self._chunking.discard(slot)

    def _prefill_call(self, slot: int, req: _Request, eff, start: int,
                      new_len: int, *, last: bool):
        """ONE prefill program call over ``eff[start:start+new_len]``
        (an unchunked admission, or one chunk slice).  ``last`` runs
        the admission bookkeeping: the call's sampled token is the
        request's next real token exactly when the slice ends at the
        prompt end — intermediate slices' samples land at positions
        nothing reads."""
        params = self.wstate["params"]
        pb = self._bucket(new_len)
        temp = np.float32(req.temperature)
        # sentinels: see _sample_slots
        topk = np.int32(req.top_k if req.top_k is not None
                        else self._vocab)
        topp = np.float32(req.top_p if req.top_p is not None else 1.0)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :new_len] = eff[start:start + new_len]
        # chunk slices (and their finals) continue from earlier
        # positions and need the full-context program; a whole-tail
        # admission at start == 0 takes the bucket-local fast variant
        fn = self._prefill_fn(pb, params,
                              full_ctx=(start > 0 or not last))
        if self.paged:
            self._caches, self._toks, first = fn(
                params, self._caches, self._toks, req.page_row, padded,
                np.int32(new_len), np.int32(start), np.int32(slot),
                temp, topk, topp, req.key_data)
        elif self._prefill_start:
            self._caches, self._toks, first = fn(
                params, self._caches, self._toks, padded,
                np.int32(new_len), np.int32(start), np.int32(slot),
                temp, topk, topp, req.key_data)
        else:
            # sealed dense artifacts from pre-chunking exports: the
            # whole-prompt calling convention (start is always 0 and
            # chunking is gated off by _chunk_capable)
            self._caches, self._toks, first = fn(
                params, self._caches, self._toks, padded,
                np.int32(new_len), np.int32(slot), temp, topk, topp,
                req.key_data)
        if not last:
            return
        if self.paged:
            self._register_prefix_pages(req)
        first = int(first)
        # int(first) above synced on the prefill result, so this is the
        # honest host-side time-to-first-token boundary
        now = time.monotonic()
        # metric label: the bucket of the WHOLE tail this admission
        # prefilled, not the final slice's — a chunked 8k prompt whose
        # last slice fit bucket 16 must not land its multi-second
        # duration in the small-prefill latency series (for unchunked
        # calls the slice IS the whole tail, so the label is ``pb``)
        lab = self._bucket(max(1, start + new_len - req.chunk_first))
        req.bucket = lab
        self._m_prefill.labels(bucket=lab).observe(
            now - req.run_started_at)
        # prefill-throughput EWMA (tokens/s over the whole tail) — the
        # fleet router's fetch-vs-reprefill payoff reads this off
        # stats()["kv_transfer"] to estimate what a local re-prefill of
        # N tokens would cost (scheduler thread only)
        rate = max(1, start + new_len - req.chunk_first) \
            / max(now - req.run_started_at, 1e-9)
        self._prefill_tok_s = rate if self._prefill_tok_s <= 0 \
            else 0.8 * self._prefill_tok_s + 0.2 * rate
        if req.first_token_at is None:
            # chunked or not, preempted-before-first-token or not: TTFT
            # is observed exactly once, at the ACTUAL first token —
            # and never for batch (SLO exclusion, see _prefill)
            req.first_token_at = now
            if not req.batch:
                self._m_ttft.labels(bucket=lab).observe(
                    now - req.submitted_at)
        P = int(eff.size)
        self._pos[slot] = P
        self._temp[slot] = temp
        self._topk[slot] = topk
        self._topp[slot] = topp
        self._eos[slot] = -1 if req.eos_id is None else req.eos_id
        # the FINAL token index is invariant across preemptions:
        # original prompt + n_steps, however much of it already sits in
        # req.gen
        self._end[slot] = req.end_index
        self._keys[slot] = req.key_data
        if self.spec:
            # drafter history: the full effective prompt (prefills never
            # write the prompt region of _toks) + the first token
            self._hist[slot, :P] = eff
            self._hist[slot, P] = first
            self._hist_pos[slot] = P
        self._tok_count.inc()
        if req.batch:
            self._batch_tok_n += 1
        if req.stream is not None:
            # the first token is already host-side (int(first) above):
            # its frame streams now, not at the next dispatch's flush
            if req.stream.push(int(req.gen.size), (first,)):
                self._m_stream_frames.inc()
        done = (P >= req.end_index
                or (req.eos_id is not None and first == req.eos_id))
        if not done and req.stop_seqs:
            tail = np.concatenate(
                [req.gen, np.asarray([first], np.int32)])
            if self._match_stop(req, tail, int(req.gen.size)) is not None:
                # the first generated token completed a stop sequence
                # (possibly one spanning into the resume prefix):
                # retiring here keeps it — same shape as an eos hit
                req.stop_hit = True
                done = True
        self._active[slot] = not done
        if done:
            self._retire(slot)

    def _advance_once(self):
        """One scheduler advance of every active slot: a speculative
        verify step when the drafter proposed AND the measured payoff
        test passes (slots without a draft ride along on their ``-1``
        rows and advance exactly one token — the decode-step
        behavior), else the plain decode step.  When even a best-case
        draft could not pay (``_spec_worthwhile``), the drafter and its
        history sync are skipped entirely — a workload the drafter
        cannot predict decays to plain decode plus one drafting attempt
        every ``_SPEC_PROBE_TICKS`` ticks (the attempt counter resets
        whether or not a draft was found, so an undraftable stream can
        never degrade to per-tick host overhead)."""
        draft = None
        if self.spec and self._spec_worthwhile():
            probe = self._ticks_since_attempt >= _SPEC_PROBE_TICKS \
                and self._verify_wall_ewma > 0
            draft = self._spec_drafts()
            self._ticks_since_attempt = 0   # attempt consumed either way
            self._spec_attempts += 1
            # a parked-regime probe that FOUND a draft runs the verify
            # unconditionally — its purpose is refreshing the accept
            # EWMA the payoff test reads; in the profitable regime the
            # per-matrix payoff test still arbitrates
            if draft is not None and not probe \
                    and not self._verify_pays(draft):
                draft = None
        if draft is not None:
            self._verify_once(draft)
        elif self._mega is not None and self._mega_ready():
            self._megastep_once()
        else:
            self._step_once()

    def _mega_ready(self) -> bool:
        """May this iteration fuse N micro-steps?  Only when nothing
        could want the scheduler back sooner: every slot busy (a free
        slot means the next arrival's admission — and any preemption
        on its behalf — would wait out the block), the queue empty,
        and no slot mid-chunked-prefill (its next slice interleaves
        with single steps).  Any pending work drops this iteration to
        N=1, so interactive latency, overload reflexes, and the
        spec-decode interleave (which already claimed this tick if a
        draft was worth verifying) never wait on a fused block."""
        # lint: disable=VC201 bool(deque) is atomic under the GIL; a
        # stale read only defers fusion by one iteration
        return (bool(self._active.all()) and not self._queue
                and not self._chunking)

    def _spec_worthwhile(self) -> bool:
        """Cheap pre-draft gate: could a verify step pay even if EVERY
        active slot drafted at the recent accept rate?  Same economics
        as :meth:`_verify_pays` with drafted == active (its upper
        bound), so a false here implies _verify_pays would refuse any
        actual draft matrix — skipping the drafter is free.  Three
        regimes: profitable (measured EWMAs, payoff positive) drafts
        every tick; cold (no verify step has measured the walls yet)
        measures-first on every tick but only for a BOUNDED attempt
        budget — a stream whose history never recurs must not pay
        drafter + history-sync per tick forever; parked (or cold
        budget spent) rations attempts to one per
        ``_SPEC_PROBE_TICKS``."""
        if self._verify_wall_ewma > 0 and self._step_wall_ewma > 0:
            ratio = self._verify_wall_ewma / max(self._step_wall_ewma,
                                                 1e-9)
            if 1 + self.spec_k * self._accept_ewma >= ratio:
                return True     # profitable regime: draft every tick
        elif self._spec_attempts < 64:
            return True         # cold phase: measure first, boundedly
        return self._ticks_since_attempt >= _SPEC_PROBE_TICKS

    def _verify_pays(self, draft) -> bool:
        """Interleave policy: one verify step must be expected to emit
        at least what the SAME wall spent on decode steps would —
        ``active + proposed·accept_ewma  >=  active · (verify wall /
        decode wall)``, all three factors measured on THIS engine (the
        verify/decode cost ratio is workload- and hardware-shaped:
        near ``1`` where per-step dispatch dominates — small models on
        CPU, bandwidth-bound decode on real accelerators — and near
        ``k+1`` where per-position compute does).  Until both EWMAs
        exist the answer is yes (measure first); re-qualification after
        parking is the probe path in :meth:`_advance_once`."""
        active = int(self._active.sum())
        # REAL proposal count, not drafted·k: rows are capped by the
        # slot's length bound and the continuation the n-gram found
        proposed = int((draft >= 0).sum())
        if self._verify_wall_ewma <= 0 or self._step_wall_ewma <= 0:
            return True
        ratio = self._verify_wall_ewma / max(self._step_wall_ewma, 1e-9)
        expected = active + proposed * self._accept_ewma
        return expected >= active * ratio

    def _spec_drafts(self):
        """(S, K) int32 draft matrix from the n-gram drafter over each
        active slot's host-side token history, or None when no slot
        drafted (the scheduler then runs a plain decode step).  ``-1``
        rows/entries never match, so an undrafted slot still advances
        one token through the verify program."""
        self._sync_hist()
        draft = None
        for s in np.flatnonzero(self._active):
            req = self._slot_req[s]
            if req is None:
                continue
            pos, end = int(self._pos[s]), int(self._end[s])
            # remaining == 1 finishes on the first emitted token: a
            # draft could accept nothing, so don't pay for one
            if end - pos < 2:
                continue
            row = ngram_draft(self._hist[s, :pos + 1], self.spec_k)
            if row is None:
                continue
            # proposals past the slot's length bound are dead weight
            keep = min(self.spec_k, end - pos - 1)
            row[keep:] = -1
            if not (row >= 0).any():
                continue
            if draft is None:
                draft = np.full((self.slots, self.spec_k), -1, np.int32)
            draft[s] = row
        return draft

    def _sync_hist(self):
        """LAZILY mirror freshly written tokens into the host-side
        history the drafter reads (one bulk D2H of the token matrix,
        paid only on ticks that actually draft — a parked speculative
        engine costs nothing per step).  ``_hist_pos`` tracks how far
        each slot's mirror is valid; the prompt region stays the host
        copy _prefill wrote, because paged prefills never write the
        possibly-shared prompt rows of ``_toks``."""
        stale = [int(s) for s in np.flatnonzero(self._active)
                 if self._hist_pos[s] < self._pos[s]]
        if not stale:
            return
        htoks = np.asarray(self._toks)
        for s in stale:
            lo, hi = int(self._hist_pos[s]), int(self._pos[s])
            self._hist[s, lo + 1:hi + 1] = htoks[s, lo + 1:hi + 1]
            self._hist_pos[s] = hi

    @staticmethod
    def _match_stop(req: _Request, gen_all, start: int):
        """Earliest count ``n`` of generated tokens to KEEP such that a
        stop sequence ends at ``gen_all[n - 1]``, scanning only match
        ends at index >= ``start`` — earlier ends were scanned at
        earlier flushes, so a sequence SPANNING a flush boundary still
        matches (its end is new even though its head streamed already).
        ``gen_all`` is every generated token including the resume
        prefix.  None = no match."""
        for j in range(int(start), int(gen_all.size)):
            for seq in req.stop_seqs:
                ln = int(seq.size)
                if ln <= j + 1 and np.array_equal(
                        gen_all[j + 1 - ln:j + 1], seq):
                    return j + 1
        return None

    def _stop_retire(self, slot: int, n_keep: int):
        """Early retirement on a stop-sequence match: the slot frees
        like any retire, the result keeps the generated tokens THROUGH
        the match (``n_keep``, counting the resume prefix), and
        ``stop_hit`` routes the terminal frame's finish reason."""
        req = self._slot_req[slot]
        self._active[slot] = False
        self._slot_req[slot] = None
        self._release_slot_pages(slot)
        req.stop_hit = True
        P = int(req.prompt.size) + int(req.gen.size)
        fresh = np.asarray(
            self._toks[slot, P:P + n_keep - int(req.gen.size)],
            np.int32)
        self._retired.inc()
        req.finish(result=np.concatenate([req.prompt, req.gen, fresh]))
        self._observe_finish(req, "ok")

    def _flush_streams(self):
        """Push every streaming slot's freshly decoded tokens as frames
        — ONE bulk token-matrix D2H per dispatch, paid only while a
        streaming request is active (the same discipline as
        :meth:`_sync_hist`).  Runs once per dispatch whatever the
        dispatch shape, so a megastep/verify block flushes its whole
        emitted run in one pass — the megastep-aware "flush every N
        micro-steps" cadence falls out for free.  Stop sequences are
        matched here BEFORE pushing, so no frame past the stop point
        ever streams."""
        htoks = None
        for slot in range(self.slots):
            req = self._slot_req[slot]
            # a mid-chunked-prefill slot still carries the PREVIOUS
            # occupant's _pos — nothing to flush until its final slice
            if req is None or req.stream is None \
                    or slot in self._chunking:
                continue
            h = req.stream
            total = int(self._pos[slot]) + 1 - int(req.prompt.size)
            start = h.next_i    # scheduler thread is the sole writer
            if total <= start:
                continue
            if htoks is None:
                htoks = np.asarray(self._toks)
            P = int(req.prompt.size)
            lim = total
            n_keep = None
            if req.stop_seqs and not req.stop_hit:
                gen_all = np.concatenate([
                    req.gen,
                    htoks[slot, P + int(req.gen.size):P + total]])
                n_keep = self._match_stop(req, gen_all, start)
                if n_keep is not None:
                    lim = n_keep
            if lim > start:
                n = h.push(start, htoks[slot, P + start:P + lim])
                if n:
                    self._m_stream_frames.inc(n)
            if n_keep is not None:
                self._stop_retire(slot, n_keep)

    def _post_step(self, finished):
        """Retirement + mid-flight deadline sweep shared by the decode
        and verify steps."""
        # stream flush FIRST: _slot_req still maps every slot that just
        # emitted, and a deadline expiry below must deliver the tokens
        # this dispatch produced before its terminal frame.
        with self._qlock:
            flush = bool(self._streams)
        if flush:
            self._flush_streams()
        now = time.monotonic()
        for slot in np.flatnonzero(np.asarray(finished)):
            self._retire(int(slot))
        # mid-flight deadline: a wedged client must not hold a slot
        for slot in np.flatnonzero(self._active):
            req = self._slot_req[slot]
            if req is not None and now > req.deadline:
                self._active[slot] = False
                self._slot_req[slot] = None
                self._release_slot_pages(int(slot))
                self._timeouts.inc()
                req.finish(error=TimeoutError(
                    "request deadline expired while decoding"))
                self._observe_finish(req, "504")

    def _note_batch_tokens(self, per_slot):
        """Attribute one dispatch's per-slot emitted counts to the
        batch-lane token total (vt_batch_tokens_per_sec's feed).
        Scheduler thread, called BEFORE _post_step — ``_slot_req``
        still maps every slot that just emitted."""
        for slot, n in enumerate(np.asarray(per_slot)):
            if n:
                req = self._slot_req[slot]
                if req is not None and req.batch:
                    self._batch_tok_n += int(n)

    def _step_once(self):
        from . import faults
        t0 = time.monotonic()
        if faults.enabled():
            plan = faults.get_plan()
            if plan.decode_stall_ms \
                    and faults.fire_once("decode_stall"):
                # injected tail-latency spike (runtime/faults.py): one
                # artificially slow decode step, inside the timed
                # window so it lands in vt_decode_step_seconds and the
                # wall EWMAs exactly like a real stall would
                time.sleep(plan.decode_stall_ms / 1e3)
        args = (self.wstate["params"], self._caches, self._toks)
        if self.paged:
            args += (self._ptab,)
        self._caches, self._toks, pos, active, finished = self._decode(
            *args, self._pos, self._active, self._temp, self._topk,
            self._topp, self._eos, self._end, self._keys)
        n_active = int(self._active.sum())
        self._decode_steps.inc()
        self._dispatches.inc()
        self._occupancy_sum += n_active
        self._tok_count.inc(n_active)
        # pre-step mask: every then-active slot emitted exactly one
        self._note_batch_tokens(self._active.astype(np.int64))
        # np.array (copy): asarray would alias the read-only device view
        self._pos = np.array(pos)
        self._active = np.array(active)
        # the np.array copies above synced on the step result, so this
        # wall time is the real per-token decode latency under load
        wall = time.monotonic() - t0
        self._m_decode_step.observe(wall)
        # bandwidth-utilization denominator: a light EWMA smooths the
        # per-step jitter without hiding a sustained slowdown
        self._step_wall_ewma = wall if self._step_wall_ewma <= 0 \
            else 0.9 * self._step_wall_ewma + 0.1 * wall
        rate = self._decode_bytes / max(wall, 1e-9)
        self._bw_ewma = rate if self._bw_ewma <= 0 \
            else 0.9 * self._bw_ewma + 0.1 * rate
        self._last_step_at = time.monotonic()
        if self.spec:
            self._ticks_since_attempt += 1
        self._post_step(finished)

    def _verify_once(self, draft):
        """One speculative verify step: every active slot scores its
        ``k + 1`` positions in one program call and advances by its
        accepted prefix + the bonus token (1 .. k+1 tokens; undrafted
        slots advance exactly 1).  Bitwise the decode path's tokens —
        the program's sampler picks every emitted token; the draft only
        decides how many picks one call makes."""
        t0 = time.monotonic()
        old_pos = self._pos.copy()
        args = (self.wstate["params"], self._caches, self._toks)
        if self.paged:
            args += (self._ptab,)
        (self._caches, self._toks, pos, active, finished,
         accepted) = self._verify(
            *args, self._pos, self._active, self._temp, self._topk,
            self._topp, self._eos, self._end, self._keys, draft)
        self._pos = np.array(pos)
        self._active = np.array(active)
        emitted = int((self._pos - old_pos).sum())
        self._tok_count.inc(emitted)
        self._note_batch_tokens(self._pos - old_pos)
        self._verify_steps += 1
        self._dispatches.inc()
        proposed = int((draft >= 0).sum())
        acc = int(np.asarray(accepted).sum())
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(acc)
        # the np.array copies synced on the result: honest wall time
        wall = time.monotonic() - t0
        self._m_spec_verify.observe(wall)
        # policy state (see _verify_pays): verify wall + accept EWMAs
        self._verify_wall_ewma = wall if self._verify_wall_ewma <= 0 \
            else 0.9 * self._verify_wall_ewma + 0.1 * wall
        if proposed:
            self._accept_ewma = (0.8 * self._accept_ewma
                                 + 0.2 * acc / proposed)
        # a verify step IS decode traffic: keep the achieved-bandwidth
        # gauge live (its cost analysis over its wall) and the idle
        # detector fed — an engine serving pure speculative load must
        # never scrape as bandwidth-0 (the decode-wall EWMA itself
        # stays decode-only: it is the payoff test's denominator)
        if self._verify_bytes > 0:
            rate = self._verify_bytes / max(wall, 1e-9)
            self._bw_ewma = rate if self._bw_ewma <= 0 \
                else 0.9 * self._bw_ewma + 0.1 * rate
        self._last_step_at = time.monotonic()
        self._post_step(finished)

    def _megastep_once(self):
        """One megastep dispatch: every slot advances up to N tokens in
        one program call, with in-program eos/length retirement between
        micro-steps (bitwise the N=1 path's tokens — same sampler, same
        per-position key folds).  The host pays ONE scheduler pass —
        retirement, deadline sweep, accounting — for the whole block:
        ``toks`` already holds each slot's emitted buffer and
        ``emitted`` its count, so :meth:`_post_step` consumes the block
        in bulk exactly like a verify step's accepted run."""
        t0 = time.monotonic()
        args = (self.wstate["params"], self._caches, self._toks)
        if self.paged:
            args += (self._ptab,)
        (self._caches, self._toks, pos, active, finished,
         emitted) = self._mega(
            *args, self._pos, self._active, self._temp, self._topk,
            self._topp, self._eos, self._end, self._keys)
        self._pos = np.array(pos)
        self._active = np.array(active)
        n_emitted = int(np.asarray(emitted).sum())
        self._tok_count.inc(n_emitted)
        self._note_batch_tokens(np.asarray(emitted))
        # per-micro-step accounting so occupancy and per-token latency
        # stay comparable across N: N micro-steps ran, their summed
        # live-slot count IS the emitted total, and the per-token wall
        # is the dispatch wall over N
        self._decode_steps.inc(self.megastep)
        self._dispatches.inc()
        self._occupancy_sum += n_emitted
        self._mega_steps += 1
        wall = time.monotonic() - t0
        per_tok = wall / self.megastep
        self._m_decode_step.observe(per_tok)
        self._step_wall_ewma = per_tok if self._step_wall_ewma <= 0 \
            else 0.9 * self._step_wall_ewma + 0.1 * per_tok
        if self._mega_bytes > 0:
            rate = self._mega_bytes / max(wall, 1e-9)
            self._bw_ewma = rate if self._bw_ewma <= 0 \
                else 0.9 * self._bw_ewma + 0.1 * rate
        self._last_step_at = time.monotonic()
        if self.spec:
            self._ticks_since_attempt += 1
        self._post_step(finished)

    def _retire(self, slot: int):
        req = self._slot_req[slot]
        self._active[slot] = False
        self._slot_req[slot] = None
        self._release_slot_pages(slot)
        if req is None:
            return
        # prefill never writes the (possibly shared) prompt region of
        # the token row, so assemble from the request's own prompt +
        # whatever a preemption already harvested + this run's tokens
        P = int(req.prompt.size) + int(req.gen.size)
        gen = np.asarray(self._toks[slot, P:int(self._pos[slot]) + 1],
                         np.int32)
        self._retired.inc()
        req.finish(result=np.concatenate([req.prompt, req.gen, gen]))
        self._observe_finish(req, "ok")

    def _maybe_report(self):
        # every tick: the SLO window ring rotates (cheap — it appends a
        # snapshot at most once per slice).  The gauges publish on the
        # 0.5s branch below, so a bare GET /metrics or /slo.json scrape
        # is never stale — no dependence on anything polling /engine or
        # a StatusReporter being attached (e.g. --serve --artifact
        # boots status-less) — while the per-decode-step hot path never
        # pays the O(pages) pool summary.
        self._slo.tick()
        # the admission controller evaluates on the same heartbeat
        # (internally rate-limited to serve.admission.interval_s): its
        # sensor is the ring the line above just rotated
        self._admission.tick()
        now = time.monotonic()
        if now - self._status_mark < 0.5:
            return
        self._status_mark = now
        stats = self.stats()    # publishes the sampled gauges
        if self.status is None:
            return
        try:
            self.status.update(engine=stats)
        except Exception:  # status must never take the engine down
            pass
