"""Pallas kernel tests — run in interpreter mode on the CPU backend,
checked against jnp references (the reference's pattern of same-math tests
across backends, veles/tests/accelerated_test.py:41-70)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.parallel.ring_attention import full_attention


@pytest.fixture
def qkv(rng):
    B, T, H, D = 2, 48, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_full(qkv, causal):
    q, k, v = qkv
    out = pk.flash_attention(q, k, v, causal, None, 16, 16, True)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_unpadded_blocks(rng):
    # T not a multiple of the block size exercises the padding/mask path.
    B, T, H, D = 1, 37, 1, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    out = pk.flash_attention(q, k, v, True, None, 16, 16, True)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_cross_attention_lengths(rng):
    B, H, D = 1, 2, 8
    q = jnp.asarray(rng.standard_normal((B, 24, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, 40, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, 40, H, D)), jnp.float32)
    out = pk.flash_attention(q, k, v, False, None, 16, 16, True)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_matches_reference(qkv):
    q, k, v = qkv

    def loss_pallas(q, k, v):
        return jnp.sum(jnp.square(
            pk.flash_attention(q, k, v, True, None, 16, 16, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=True)))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal,tq,tk", [(True, 37, 37), (False, 24, 40)])
def test_flash_attention_grad_padded_and_cross(rng, causal, tq, tk):
    """Backward kernels must mask padded Q rows (their lse is bogus) and
    handle Tq != Tk — the failure surfaces of the dq/dkv Pallas kernels."""
    B, H, D = 1, 2, 8
    q = jnp.asarray(rng.standard_normal((B, tq, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, tk, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, tk, H, D)), jnp.float32)

    def loss_pallas(q, k, v):
        return jnp.sum(jnp.square(
            pk.flash_attention(q, k, v, causal, None, 16, 16, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(full_attention(q, k, v, causal=causal)))

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_fused_dropout_rate_and_scaling(rng):
    x = jnp.ones((64, 128), jnp.float32)
    out = pk.fused_dropout(x, 7, 0.4, 32, True)
    out = np.asarray(out)
    kept = out != 0
    assert abs(kept.mean() - 0.6) < 0.05
    np.testing.assert_allclose(out[kept], 1.0 / 0.6, rtol=1e-6)


def test_fused_dropout_deterministic_per_seed(rng):
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    a = pk.fused_dropout(x, 3, 0.5, 16, True)
    b = pk.fused_dropout(x, 3, 0.5, 16, True)
    c = pk.fused_dropout(x, 4, 0.5, 16, True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_fused_dropout_grad_uses_same_mask(rng):
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    out = pk.fused_dropout(x, 11, 0.3, 16, True)
    g = jax.grad(lambda x_: jnp.sum(
        pk.fused_dropout(x_, 11, 0.3, 16, True)))(x)
    mask = np.asarray(out) != 0
    expect = np.where(mask, 1.0 / 0.7, 0.0)
    np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-6)


def test_gather_rows_matches_take(rng):
    data = rng.standard_normal((40, 3, 7)).astype(np.float32)
    idx = rng.integers(0, 40, 13).astype(np.int32)
    out = pk.gather_rows(jnp.asarray(data), jnp.asarray(idx), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), data[idx])


def test_blockwise_attention_flash_delegation(rng):
    from veles_tpu.parallel.ring_attention import blockwise_attention
    B, T, H, D = 1, 40, 2, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    out = blockwise_attention(q, k, v, block_size=16, causal=True,
                              use_flash=True)
    ref = blockwise_attention(q, k, v, block_size=16, causal=True,
                              use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_fused_dropout_stream_statistics():
    """The single-pass fmix32 counter RNG must give per-seed rate
    concentration, decorrelated masks across seeds, and no row/column
    structure from the linear-index hashing."""
    x = jnp.ones((512, 1024))
    for seed in range(4):
        o = pk.fused_dropout(x, seed, 0.4, 256, True)
        assert abs(float(jnp.mean(o != 0)) - 0.6) < 0.01
    m0 = np.asarray(pk.fused_dropout(x, 0, 0.4, 256, True) != 0)
    m1 = np.asarray(pk.fused_dropout(x, 1, 0.4, 256, True) != 0)
    # independent Bernoulli(0.6) masks agree with prob 0.6^2 + 0.4^2
    assert abs((m0 == m1).mean() - 0.52) < 0.02
    assert m0.mean(1).std() < 0.03 and m0.mean(0).std() < 0.03


def _windowed_reference(q, k, v, window):
    """Dense causal sliding-window attention reference."""
    return _dense_reference(q, k, v, True, window)


@pytest.mark.parametrize("T,window", [(96, 32), (100, 16), (64, 64)])
def test_flash_attention_sliding_window(rng, T, window):
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, 2, 16)), jnp.float32)
               for _ in range(3))
    out = pk.flash_attention(q, k, v, True, None, 16, 16, True, window)
    ref = _windowed_reference(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_sliding_window_grads(rng):
    T, window = 96, 32
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, 2, 16)), jnp.float32)
               for _ in range(3))
    gp = jax.grad(lambda a, b, c: jnp.sum(pk.flash_attention(
        a, b, c, True, None, 16, 16, True, window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        _windowed_reference(a, b, c, window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_window_requires_causal(rng):
    q = jnp.ones((1, 32, 1, 8))
    with pytest.raises(ValueError):
        pk.flash_attention(q, q, q, False, None, 16, 16, True, 8)


@pytest.mark.parametrize("G", [2, 4])
def test_flash_attention_gqa(rng, G):
    """Grouped-query attention: kernel with shared kv heads must equal
    the full-attention reference on repeated kv."""
    B, T, Hk, D = 1, 64, 2, 16
    H = Hk * G
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, T, Hk, D)), jnp.float32)
            for _ in range(2))
    from veles_tpu.parallel.ring_attention import full_attention
    kf = jnp.repeat(k, G, axis=2)
    vf = jnp.repeat(v, G, axis=2)
    out = pk.flash_attention(q, k, v, True, None, 16, 16, True)
    ref = full_attention(q, kf, vf, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)

    # grads: dk/dv must come back kv-head shaped and equal the grouped
    # sums of the full-head reference grads
    gp = jax.grad(lambda a, b, c: jnp.sum(pk.flash_attention(
        a, b, c, True, None, 16, 16, True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(
        full_attention(a, b, c, causal=True) ** 2),
        argnums=(0, 1, 2))(q, kf, vf)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gr[0]),
                               rtol=2e-4, atol=2e-5)
    for gi, ri in ((1, 1), (2, 2)):
        grouped = np.asarray(gr[ri]).reshape(B, T, Hk, G, D).sum(3)
        np.testing.assert_allclose(np.asarray(gp[gi]), grouped,
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_gqa_with_window(rng):
    B, T, Hk, G, D, W = 1, 96, 2, 2, 16, 32
    q = jnp.asarray(rng.standard_normal((B, T, Hk * G, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, T, Hk, D)), jnp.float32)
            for _ in range(2))
    out = pk.flash_attention(q, k, v, True, None, 16, 16, True, W)
    ref = _windowed_reference(q, jnp.repeat(k, G, 2), jnp.repeat(v, G, 2),
                              W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


# -- flash attention by tile class --------------------------------------------

def _dense_reference(q, k, v, causal, window):
    """Dense attention with absolute-position causal/window masks and
    grouped kv heads: the position-by-position statement of what the
    flash kernels' tile classes must add up to."""
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    mask = _pair_mask(q.shape[1], k.shape[1], q.shape[1], k.shape[1],
                      causal, window)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _pair_mask(tq_p, tk_p, tq, tk, causal, window):
    """(tq_p, tk_p) bool: which (query, key) pairs attend, padding out."""
    qpos = np.arange(tq_p)[:, None]
    kpos = np.arange(tk_p)[None, :]
    mask = (qpos < tq) & (kpos < tk)
    if causal:
        mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
    return mask


# (tq, tk, block_q, block_k, causal, window, (G, D) or None): the last
# names kv groups and head size of an interpret-mode run against the
# dense reference; None where only the classes are checked (T = 2048 is
# the benchmark's shape, too slow for the interpreter)
_TILE_CASES = {
    "lm_512x512": (2048, 2048, 512, 512, True, None, None),
    "lm_256x1024": (2048, 2048, 256, 1024, True, None, None),
    "lm_256x256": (2048, 2048, 256, 256, True, None, None),
    "lm_window": (2048, 2048, 256, 512, True, 600, None),
    "causal": (48, 48, 16, 16, True, None, (1, 16)),
    "causal_tall_blocks": (64, 64, 32, 16, True, None, (1, 16)),
    "causal_wide_blocks": (64, 64, 16, 32, True, None, (1, 8)),
    "window": (96, 96, 16, 16, True, 32, (1, 16)),
    "window_wide_blocks": (64, 64, 16, 32, True, 24, (1, 16)),
    "window_padded": (100, 100, 16, 16, True, 16, (1, 16)),
    "gqa2": (64, 64, 16, 16, True, None, (2, 16)),
    "gqa4_window": (96, 96, 16, 16, True, 32, (4, 16)),
    "cross_padded": (24, 40, 16, 16, False, None, (1, 8)),
    "cross_causal_short_q": (17, 40, 16, 8, True, None, (1, 16)),
    "cross_causal_long_q": (40, 24, 16, 16, True, None, (2, 16)),
    "padded": (37, 37, 16, 16, True, None, (1, 8)),
    "noncausal": (48, 48, 16, 16, False, None, (1, 16)),
    # K blocks wider than a register's 128 lanes, head wider too: the
    # lane-replicated row state is tiled, not sliced (pk._lanes)
    "wide_lanes": (256, 256, 128, 256, True, None, (1, 256)),
    "odd_lanes": (192, 192, 64, 192, True, None, (1, 8)),
}
_TILE_COUNTS = {  # ISSUE 28: what vt_flash_tiles must read
    "lm_512x512": {"dead": 6, "edge": 4, "interior": 6},
    "lm_256x1024": {"dead": 4, "edge": 8, "interior": 4},
    "lm_256x256": {"dead": 28, "edge": 8, "interior": 28},
    "noncausal": {"dead": 0, "edge": 0, "interior": 9},
}


@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_flash_tile_classes(rng, case):
    """The kernels' tile predicates against a brute-force mask: every
    interior tile is all-true (and every all-true tile interior), every
    dead tile all-false, a clamped block index names a live tile and
    repeats over consecutive dead steps; and the classed kernels'
    forward and three gradients equal the dense reference."""
    tq, tk, bq_req, bk_req, causal, window, run = _TILE_CASES[case]
    bq, bk, tq_p, tk_p = pk._flash_blocks(tq, tk, bq_req, bk_req)
    n_qb, n_kb = tq_p // bq, tk_p // bk
    geom = dict(causal=causal, window=window, block_q=bq, block_k=bk)
    mask = _pair_mask(tq_p, tk_p, tq, tk, causal, window)
    counts = {"dead": 0, "edge": 0, "interior": 0}
    live = np.zeros((n_qb, n_kb), bool)
    for qi in range(n_qb):
        for kj in range(n_kb):
            tile = mask[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk]
            live[qi, kj] = pk._flash_block_live(qi, kj, **geom) \
                is not False
            interior = bool(pk._flash_tile_interior(qi, kj, tq=tq, tk=tk,
                                                    **geom))
            assert interior == tile.all(), (qi, kj)
            assert live[qi, kj] or not tile.any(), (qi, kj)
            assert live[qi, kj] or not interior
            if tq == tk:  # no tile is live through padded rows alone
                assert live[qi, kj] == tile.any(), (qi, kj)
            counts["interior" if interior else
                   "edge" if live[qi, kj] else "dead"] += 1
    assert pk.flash_tile_classes(tq, tk, bq_req, bk_req, causal,
                                 window) == counts
    assert counts == _TILE_COUNTS.get(case, counts)
    # the index maps' clamps: identity on a live tile, else the nearest
    # live block of the sweep (so a dead step fetches nothing new)
    for sweep, clamp in (
            (live, lambda i, j: pk._flash_live_k(i, j, n_kb=n_kb, **geom)),
            (live.T, lambda j, i: pk._flash_live_q(j, i, n_qb=n_qb,
                                                   **geom))):
        for a, row in enumerate(sweep):
            got = [int(clamp(a, b)) for b in range(len(row))]
            assert all(0 <= g < len(row) for g in got)
            if not row.any():
                continue
            alive = np.flatnonzero(row)
            want = np.clip(np.arange(len(row)), alive[0], alive[-1])
            assert got == list(want), (a, got)
    if run is None:
        return
    G, D = run
    q = jnp.asarray(rng.standard_normal((1, tq, 2 * G, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, tk, 2, D)), jnp.float32)
            for _ in range(2))

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.square(attend(q, k, v)))

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal, None, bq_req, bk_req,
                                  True, window)

    def dense(q, k, v):
        return _dense_reference(q, k, v, causal, window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


# -- flash attention in the projections' own layout ---------------------------

# ((B, T, H, H_kv, D), (block_q, block_k), causal, window) -> heads a grid
# step takes out of (B, T, H * D); 0: the transposed copies
_LAYOUT_CASES = {
    "d64_mha_two_heads": ((2, 64, 4, 4, 64), (16, 32), True, None, 2),
    "d64_mha_noncausal": ((1, 32, 2, 2, 64), (16, 16), False, None, 2),
    "d32_mha_four_heads": ((1, 32, 4, 4, 32), (16, 16), True, None, 4),
    "d16_mha_eight_heads": ((1, 32, 8, 8, 16), (16, 16), True, None, 8),
    "d128_gqa_32_4": ((1, 32, 32, 4, 128), (16, 16), True, None, 0),
    "d128_gqa_32_4_window": ((1, 48, 32, 4, 128), (16, 16), True, 24, 0),
    "d128_mha_noncausal": ((1, 32, 2, 2, 128), (16, 16), False, None, 0),
    "d80": ((1, 32, 2, 2, 80), (16, 16), True, None, 0),
    "d64_odd_heads": ((1, 32, 3, 3, 64), (16, 16), True, None, 0),
    "d64_gqa": ((1, 32, 4, 2, 64), (16, 16), True, None, 0),
    "d64_ragged_t": ((1, 40, 2, 2, 64), (16, 16), True, None, 0),
    "d128_ragged_t": ((1, 40, 2, 1, 128), (16, 16), True, 16, 0),
}


@pytest.fixture
def transposed_path(monkeypatch):
    """Steer a call the lanes path would take onto the transposed copies,
    to compare the two: nothing in the program selects the layout but the
    shapes, so the test replaces the rule (and drops the jitted calls'
    traces, which hold the path they were traced with)."""
    def clear():
        pk._flash_fwd.clear_cache()
        pk._flash_bwd.clear_cache()

    def steer():
        monkeypatch.setattr(pk, "flash_heads_per_step", lambda *a: 0)
        clear()
    yield steer
    monkeypatch.undo()
    clear()


@pytest.mark.parametrize("case", sorted(_LAYOUT_CASES))
def test_flash_layout_rule(case):
    (B, T, H, Hk, D), blocks, _, _, heads = _LAYOUT_CASES[case]
    assert pk.flash_heads_per_step((B, T, H, D), (B, T, Hk, D),
                                   *blocks) == heads


@pytest.mark.parametrize("shape,blocks,heads", [
    ((4, 2048, 16, 16, 64), (1024, 1024), 2),     # opt350m_train_t2048
    ((16, 512, 16, 16, 64), (512, 512), 2),       # opt350m_train_t512
    ((1, 4096, 32, 4, 128), (512, 512), 0),       # trinity_mini_train_t4096
    ((4, 2048, 16, 16, 64), (256, 1024), 2),      # the default blocks
    ((4, 2000, 16, 16, 64), (256, 1024), 0),      # T the blocks do not divide
    ((4, 2048, 16, 8, 64), (512, 512), 0),        # GQA below 128 lanes
    ((4, 2048, 20, 20, 96), (512, 512), 0),       # D neither divides 128
    ((1, 4096, 8, 8, 256), (512, 512), 0),        # whole lane blocks a head
    ((2, 1024, 8, 8, 32), (512, 512), 4),         # four heads a lane block
])
def test_flash_layout_rule_at_the_cells_shapes(shape, blocks, heads):
    B, T, H, Hk, D = shape
    assert pk.flash_heads_per_step((B, T, H, D), (B, T, Hk, D),
                                   *blocks) == heads


@pytest.mark.parametrize("case", sorted(_LAYOUT_CASES))
def test_flash_layout_matches_reference(rng, case, transposed_path):
    """Forward, dq, dk and dv of a call on the path its shapes give it:
    against the dense float32 reference, and, where it reads the
    projections' own layout, against the same call over transposed
    copies; ``vt_flash_layout`` says which path it took."""
    from veles_tpu.runtime.metrics import registry
    (B, T, H, Hk, D), (bq, bk), causal, window, heads = _LAYOUT_CASES[case]
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, T, Hk, D)), jnp.float32)
            for _ in range(2))

    def loss(attend):
        return lambda q, k, v: jnp.sum(jnp.square(attend(q, k, v)))

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal, None, bq, bk, True,
                                  window)

    def dense(q, k, v):
        return _dense_reference(q, k, v, causal, window)

    def run():
        return (flash(q, k, v),
                *jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v))

    got = run()
    layout = registry().get("vt_flash_layout")
    per_step = registry().get("vt_flash_heads_per_step")
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert layout.labels(kernel=kernel, layout="lanes").value \
            == int(heads > 0)
        assert layout.labels(kernel=kernel, layout="transposed").value \
            == int(heads == 0)
        assert per_step.labels(kernel=kernel).value == max(heads, 1)
    want = (dense(q, k, v),
            *jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v))
    for a, b, tol in zip(got, want, (2e-4, 2e-3, 2e-3, 2e-3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol / 10)
    if not heads:
        return
    transposed_path()
    for a, b in zip(got, run()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def _tpu_custom_calls(fn, *shapes, grad=True):
    """``tpu_custom_call``s in the module ``fn`` (its gradient) lowers to
    for a TPU, on this CPU: each is one Mosaic lowering."""
    if grad:
        fn = jax.grad(lambda *a, _f=fn: jnp.sum(_f(*a).astype(jnp.float32)),
                      argnums=(0, 1, 2))
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes]
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text().count("tpu_custom_call")


@pytest.mark.parametrize("grad,distinct,calls", [
    (True, 1, 3), (False, 1, 1), (True, 2, 6)])
def test_flash_equal_layers_lower_once(grad, distinct, calls):
    """Twelve equal attention layers hold one lowering of each kernel,
    not twelve (0.1 s each at every start: PERF.md section 6, PR 33); a
    second shape (a window) brings its own three."""
    def stack(q, k, v):
        for i in range(12):
            window = 128 if distinct == 2 and i % 2 else None
            q = q + pk.flash_attention(q, k, v, True, None, 128, 128,
                                       False, window)
        return q
    shape = (1, 256, 4, 64)
    assert _tpu_custom_calls(stack, shape, shape, shape, grad=grad) == calls


# -- paged-attention decode kernel -------------------------------------------

def _paged_reference(q, pool_k, pool_v, ptab, pos, window=None):
    """The gather-path math `_attn_decode_step` runs: flatten each
    row's pages to the (B, L, Hk, Dh) logical view, mask, one-shot
    softmax.  THE bounded-error contract the fused kernel is pinned
    against (tolerances below are the contract)."""
    B, H, Dh = q.shape
    rows, psz, Hk, _ = pool_k.shape
    G = H // Hk
    n_ptab = ptab.shape[1]
    L = n_ptab * psz
    kf = pool_k[ptab].reshape(B, L, Hk, Dh).astype(jnp.float32)
    vf = pool_v[ptab].reshape(B, L, Hk, Dh).astype(jnp.float32)
    qg = q.reshape(B, Hk, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kf) * (Dh ** -0.5)
    t = jnp.arange(L)
    mask = t[None, :] <= pos[:, None]
    if window is not None:
        mask &= t[None, :] > pos[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p, vf).reshape(B, H, Dh)


def _paged_case(rng, B=3, Hk=2, G=2, Dh=8, psz=4, n_ptab=5):
    H = Hk * G
    rows = B * n_ptab + 1                    # + scratch page
    pool_k = jnp.asarray(rng.standard_normal((rows, psz, Hk, Dh)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((rows, psz, Hk, Dh)),
                         jnp.float32)
    ptab = jnp.asarray(
        rng.permutation(rows - 1)[:B * n_ptab].reshape(B, n_ptab),
        jnp.int32)
    pos = jnp.asarray(rng.integers(0, n_ptab * psz, B), jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    return q, pool_k, pool_v, ptab, pos


def test_paged_attention_decode_bounded_error(rng):
    """The fused kernel vs the gather-path reference: per-slot page
    tables, mixed per-row positions, GQA grouping.  Online softmax
    reorders the summation, so the contract is bounded error at these
    pinned tolerances — never bitwise (docs/serving.md)."""
    q, pk_, pv_, ptab, pos = _paged_case(rng)
    out = pk.paged_attention_decode(q, pk_, pv_, ptab, pos,
                                    page_size=4, n_kv_heads=2)
    ref = _paged_reference(q, pk_, pv_, ptab, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_decode_window_and_edges(rng):
    """Sliding window (whole pages skipped at both ends) and the
    position edges: pos = 0 (only one key live) and pos = L - 1 (every
    page live)."""
    q, pk_, pv_, ptab, _ = _paged_case(rng)
    L = ptab.shape[1] * 4
    pos = jnp.asarray([0, L - 1, 7], jnp.int32)
    for w in (None, 6):
        out = pk.paged_attention_decode(q, pk_, pv_, ptab, pos,
                                        page_size=4, n_kv_heads=2,
                                        window=w)
        ref = _paged_reference(q, pk_, pv_, ptab, pos, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6, err_msg=str(w))


def test_paged_attention_decode_under_jit_and_scratch_rows(rng):
    """jit'd (the decode program wraps it) and with page-table rows
    pointing at the scratch page beyond each slot's span — masked off
    by pos, exactly how the engine maps unassigned logical pages."""
    q, pk_, pv_, ptab, _ = _paged_case(rng, B=2, n_ptab=4)
    scratch = pk_.shape[0] - 1
    ptab = ptab.at[:, 2:].set(scratch)       # span = 2 pages per row
    pos = jnp.asarray([3, 6], jnp.int32)     # inside the real span
    out = jax.jit(lambda *a: pk.paged_attention_decode(
        *a, page_size=4, n_kv_heads=2))(q, pk_, pv_, ptab, pos)
    ref = _paged_reference(q, pk_, pv_, ptab, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_paged_attention_decode_validation(rng):
    q, pk_, pv_, ptab, pos = _paged_case(rng)
    with pytest.raises(ValueError, match="page size"):
        pk.paged_attention_decode(q, pk_, pv_, ptab, pos,
                                  page_size=8, n_kv_heads=2)
    with pytest.raises(ValueError, match="kv heads"):
        pk.paged_attention_decode(q, pk_, pv_, ptab, pos,
                                  page_size=4, n_kv_heads=4)
