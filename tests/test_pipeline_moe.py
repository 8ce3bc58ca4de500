"""Pipeline parallelism + expert parallelism on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from veles_tpu.parallel import (MeshSpec, init_moe_params, make_mesh,
                                moe_apply, moe_shardings, pipeline_apply,
                                pipeline_stage_shardings,
                                stack_stage_params)


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def test_pipeline_matches_sequential(rng):
    S, M, mb, D = 4, 8, 8, 16
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    keys = jax.random.split(jax.random.key(0), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3,
                  "b": jnp.zeros((D,))} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    got = pipeline_apply(_stage_fn, stacked, x, mesh, n_microbatches=M)

    # sequential reference
    ref = x
    for p in per_stage:
        ref = jax.vmap(lambda xi: _stage_fn(p, xi))(ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_pipeline_grad_flows(rng):
    """The pipelined forward must be differentiable (training path)."""
    S, M, mb, D = 2, 2, 4, 8
    mesh = make_mesh(MeshSpec(data=4, pipe=2))
    keys = jax.random.split(jax.random.key(1), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3,
                  "b": jnp.zeros((D,))} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    def loss(params):
        y = pipeline_apply(_stage_fn, params, x, mesh, n_microbatches=M)
        return jnp.sum(jnp.square(y))

    g = jax.grad(loss)(stacked)
    assert float(jnp.abs(g["w"]).sum()) > 0
    # per-stage grads must differ (each stage saw different activations)
    assert not np.allclose(np.asarray(g["w"][0]), np.asarray(g["w"][1]))


def test_pipeline_heterogeneous_stages(rng):
    """Round-2: stages with different parameter structures (list of
    stage_fns), verified against the sequential composition."""
    from veles_tpu.parallel.pipeline import bubble_fraction
    S, M, mb, D = 4, 8, 4, 12
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    key = jax.random.key(3)
    hiddens = [8, 24, 16, 4]  # deliberately different widths per stage

    def make_stage(k, h):
        k1, k2 = jax.random.split(k)
        return ({"w1": jax.random.normal(k1, (D, h)) * 0.4,
                 "w2": jax.random.normal(k2, (h, D)) * 0.4},
                lambda p, x: x + jax.nn.relu(x @ p["w1"]) @ p["w2"])

    params, fns = zip(*[make_stage(k, h) for k, h in
                        zip(jax.random.split(key, S), hiddens)])
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    got = pipeline_apply(list(fns), list(params), x, mesh)

    ref = x
    for p, f in zip(params, fns):
        ref = f(p, ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    assert 0.0 < bubble_fraction(S, M) < 1.0

    # gradient flows through every heterogeneous stage
    def loss(ps):
        return jnp.sum(jnp.square(pipeline_apply(list(fns), list(ps),
                                                 x, mesh)))

    gs = jax.grad(loss)(tuple(params))
    for g in gs:
        assert float(jnp.abs(g["w1"]).sum()) > 0


def test_pipeline_io_sharded(rng):
    """Round-2: inputs/outputs are sharded over the pipe axis, not
    replicated — per-device memory drops S× (the round-1 verdict's
    pipeline weakness #6)."""
    S, M, mb, D = 4, 8, 4, 8
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    keys = jax.random.split(jax.random.key(0), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    out = pipeline_apply(lambda p, x: jnp.tanh(x @ p["w"]), stacked, x,
                         mesh)
    # the output's microbatch axis must be partitioned over 'pipe'
    spec = out.sharding.spec
    assert spec and spec[0] == "pipe", spec
    shard_bytes = max(s.data.nbytes for s in out.addressable_shards)
    assert shard_bytes <= out.nbytes // S


def _dense_moe_reference(params, x):
    """Per-token expert FFN without capacity limits."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    expert = jnp.argmax(probs, -1)
    gate = jnp.take_along_axis(probs, expert[:, None], 1)[:, 0]
    outs = []
    for t in range(x.shape[0]):
        e = int(expert[t])
        h = jax.nn.relu(x[t] @ params["w1"][e])
        outs.append((h @ params["w2"][e]) * gate[t])
    return jnp.stack(outs)


def test_moe_matches_dense_reference(rng):
    T, D, H, E = 16, 8, 12, 4
    params = init_moe_params(jax.random.key(0), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    # capacity_factor high enough that nothing drops
    y, aux = moe_apply(params, x, capacity_factor=8.0)
    ref = _dense_moe_reference(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) >= 1.0  # >= 1 by Cauchy-Schwarz, = E at collapse


def test_moe_capacity_drops_tokens(rng):
    T, D, H, E = 16, 8, 12, 2
    params = init_moe_params(jax.random.key(0), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    y_full, _ = moe_apply(params, x, capacity_factor=8.0)
    y_cap, _ = moe_apply(params, x, capacity_factor=0.25)  # C=2 per expert
    dropped = np.asarray(jnp.all(y_cap == 0, axis=-1))
    assert dropped.sum() >= T - 2 * E * 2  # most tokens over capacity
    kept = ~dropped
    np.testing.assert_allclose(np.asarray(y_cap)[kept],
                               np.asarray(y_full)[kept], rtol=1e-4,
                               atol=1e-5)


def test_moe_sharded_execution(rng):
    """Expert banks sharded over the expert axis; jit runs under the mesh
    (GSPMD inserts the dispatch all_to_all)."""
    mesh = make_mesh(MeshSpec(data=2, expert=4))
    T, D, H, E = 32, 8, 16, 4
    params = init_moe_params(jax.random.key(0), E, D, H)
    params = jax.device_put(params, moe_shardings(params, mesh))
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    y, aux = jax.jit(lambda p, x: moe_apply(p, x))(params, x)
    ref, _ = moe_apply(jax.tree.map(np.asarray, params), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def _dense_topk_reference(params, x, k):
    """Per-token top-k expert mix, renormalized gates, no capacity."""
    logits = x @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = topv / topv.sum(-1, keepdims=True)
    outs = []
    for t in range(x.shape[0]):
        acc = 0.0
        for j in range(k):
            e = int(topi[t, j])
            h = jax.nn.relu(x[t] @ params["w1"][e])
            acc = acc + (h @ params["w2"][e]) * gates[t, j]
        outs.append(acc)
    return jnp.stack(outs)


def test_moe_top2_matches_dense_reference(rng):
    """Round-2 top-k routing: at ample capacity the capacity-limited
    dispatch equals the dense per-token top-2 mix."""
    T, D, H, E = 16, 8, 12, 4
    params = init_moe_params(jax.random.key(1), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    y, aux = moe_apply(params, x, capacity_factor=8.0, top_k=2)
    ref = _dense_topk_reference(params, x, 2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_top2_slot_priority_under_capacity(rng):
    """GShard slot priority: ALL first choices queue before ANY second
    choice, so under tight capacity every secondary route drops while
    every primary survives (token-major queueing would interleave them
    and drop some primaries — this test catches that regression)."""
    T, D, H, E = 8, 4, 12, 2
    params = init_moe_params(jax.random.key(2), E, D, H)
    # craft the router: tokens 0..T/2-1 -> primary e0/secondary e1,
    # tokens T/2.. -> primary e1/secondary e0; both experts' queues get
    # T/2 primaries + T/2 secondaries
    router = np.zeros((D, E), np.float32)
    router[0, 0], router[0, 1] = 2.0, 1.0
    params = {**params, "router": jnp.asarray(router)}
    x = np.abs(rng.standard_normal((T, D))).astype(np.float32)
    x[T // 2:, 0] *= -1.0  # sign of feature 0 flips the primary expert
    x = jnp.asarray(x)
    # C = cf*T*K/E = 0.5*T -> exactly all primaries fit, all secondaries
    # overflow
    y, _ = moe_apply(params, x, capacity_factor=0.5, top_k=2)

    # expected: each token keeps ONLY its primary route (with the top-2
    # renormalized gate)
    logits = np.asarray(x @ jnp.asarray(router))
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    prim = probs.argmax(-1)
    gates = np.sort(probs, -1)[:, ::-1]
    g0 = gates[:, 0] / gates.sum(-1)
    expect = []
    for t in range(T):
        h = np.maximum(np.asarray(x[t] @ params["w1"][prim[t]]), 0)
        expect.append(h @ np.asarray(params["w2"][prim[t]]) * g0[t])
    np.testing.assert_allclose(np.asarray(y), np.stack(expect),
                               rtol=1e-4, atol=1e-5)


def test_moe_router_grads_flow_topk(rng):
    T, D, H, E = 16, 8, 12, 4
    params = init_moe_params(jax.random.key(3), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    for k in (1, 2):
        g = jax.grad(lambda p: jnp.sum(
            moe_apply(p, x, top_k=k)[0] ** 2))(params)
        assert float(jnp.abs(g["router"]).sum()) > 0, k


def _mean_mse(y, t):
    return jnp.mean(jnp.square(y - t))


def test_pipeline_1f1b_matches_autodiff(rng):
    """The hand-scheduled 1F1B step must produce the same loss and stage
    grads as jax.grad through the sequential reference."""
    from veles_tpu.parallel import pipeline_train_step
    S, M, mb, D = 4, 8, 8, 16
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    keys = jax.random.split(jax.random.key(2), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3,
                  "b": jnp.zeros((D,))} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    loss, grads = pipeline_train_step(_stage_fn, _mean_mse, stacked, x, t,
                                      mesh)

    def ref_loss(params):
        total = 0.0
        for m in range(M):
            h = x[m]
            for s in range(S):
                h = _stage_fn(jax.tree.map(lambda a: a[s], params), h)
            total = total + _mean_mse(h, t[m])
        return total / M

    ref_l = ref_loss(stacked)
    # grads contract: d(mean-over-microbatches loss)/dp — the same pair
    # jax.value_and_grad over pipeline_apply would produce
    ref_g = jax.grad(ref_loss)(stacked)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(ref_g[k]),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_1f1b_data_sharded(rng):
    """1F1B with the microbatch dim sharded over the data axis: grads and
    loss must match the unsharded run."""
    from veles_tpu.parallel import pipeline_train_step
    S, M, mb, D = 2, 4, 8, 8
    mesh = make_mesh(MeshSpec(data=4, pipe=2))
    keys = jax.random.split(jax.random.key(3), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3,
                  "b": jnp.zeros((D,))} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    l_dp, g_dp = pipeline_train_step(_stage_fn, _mean_mse, stacked, x, t,
                                     mesh, batch_axes=("data",))
    l_ref, g_ref = pipeline_train_step(_stage_fn, _mean_mse, stacked, x, t,
                                       mesh)
    np.testing.assert_allclose(float(l_dp), float(l_ref), rtol=2e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g_dp[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=2e-4, atol=2e-5)


def test_pipeline_1f1b_heterogeneous(rng):
    """1F1B over different per-stage callables/param structures."""
    from veles_tpu.parallel import pipeline_train_step
    S, M, mb, D = 4, 4, 4, 8
    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    k0, k1, k2, k3 = jax.random.split(jax.random.key(4), 4)
    fns = [
        lambda p, x: jnp.tanh(x @ p["w"]),
        lambda p, x: jax.nn.relu(x @ p["a"] + p["c"]),
        lambda p, x: x * p["scale"] + p["shift"],
        lambda p, x: jnp.tanh(x @ p["w"] + p["b"]),
    ]
    params = [
        {"w": jax.random.normal(k0, (D, D)) * 0.3},
        {"a": jax.random.normal(k1, (D, D)) * 0.3, "c": jnp.zeros((D,))},
        {"scale": jnp.ones((D,)) * 1.1, "shift": jnp.zeros((D,))},
        {"w": jax.random.normal(k3, (D, D)) * 0.3, "b": jnp.zeros((D,))},
    ]
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    loss, grads = pipeline_train_step(fns, _mean_mse, params, x, t, mesh)

    def ref_loss(ps):
        total = 0.0
        for m in range(M):
            h = x[m]
            for fn, p in zip(fns, ps):
                h = fn(p, h)
            total = total + _mean_mse(h, t[m])
        return total / M

    np.testing.assert_allclose(float(loss), float(ref_loss(params)),
                               rtol=2e-5)
    # grads come back in the caller's per-stage structures
    ref_g = jax.grad(ref_loss)(params)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_g)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-5)

    # stage count mismatch raises (not silently-wrong grads)
    with pytest.raises(ValueError):
        pipeline_train_step(fns * 2, _mean_mse, params * 2, x, t, mesh)


def test_pipeline_1f1b_bounded_memory(rng):
    """The 1F1B step's compiled temp memory must beat AD-through-GPipe at
    high microbatch count (the bounded-stash property: K=2(S-1)+1 stashed
    inputs vs a tape of O(n_mb) scan carries)."""
    from veles_tpu.parallel import pipeline_train_step
    S, M, mb, D = 4, 32, 8, 64
    mesh = make_mesh(MeshSpec(pipe=4))
    keys = jax.random.split(jax.random.key(5), S)
    stacked = stack_stage_params(
        [{"w": jax.random.normal(k, (D, D)) * 0.3, "b": jnp.zeros((D,))}
         for k in keys])
    x = jnp.ones((M, mb, D), jnp.float32)
    t = jnp.zeros((M, mb, D), jnp.float32)

    def gpipe_loss(params):
        y = pipeline_apply(_stage_fn, params, x, mesh, n_microbatches=M)
        return jnp.mean(jnp.square(y - t))

    def mse(y, tt):
        return jnp.mean(jnp.square(y - tt))

    m_gpipe = jax.jit(jax.grad(gpipe_loss)).lower(stacked).compile() \
        .memory_analysis()
    m_1f1b = jax.jit(lambda p: pipeline_train_step(
        _stage_fn, mse, p, x, t, mesh)).lower(stacked).compile() \
        .memory_analysis()
    assert m_1f1b.temp_size_in_bytes < m_gpipe.temp_size_in_bytes, (
        m_1f1b.temp_size_in_bytes, m_gpipe.temp_size_in_bytes)


@pytest.mark.parametrize("S,M", [(2, 2), (2, 6), (8, 8), (8, 16)])
def test_pipeline_1f1b_schedule_sweep(rng, S, M):
    """1F1B loss matches the sequential reference across depths and
    microbatch counts (fill/drain edge cases)."""
    from veles_tpu.parallel import pipeline_train_step
    mb, D = 4, 8
    mesh = make_mesh(MeshSpec(pipe=S))
    keys = jax.random.split(jax.random.key(6), S)
    per_stage = [{"w": jax.random.normal(k, (D, D)) * 0.3,
                  "b": jnp.zeros((D,))} for k in keys]
    stacked = stack_stage_params(per_stage)
    x = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((M, mb, D)), jnp.float32)

    loss, _ = pipeline_train_step(_stage_fn, _mean_mse, stacked, x, t,
                                  mesh)
    total = 0.0
    for m in range(M):
        h = x[m]
        for s in range(S):
            h = _stage_fn(per_stage[s], h)
        total += float(_mean_mse(h, t[m]))
    np.testing.assert_allclose(float(loss), total / M, rtol=2e-5)


@pytest.mark.slow  # brute-force sort-vs-dense dispatch sweep (~19s); moe
# router/aux coverage stays tier-1
def test_moe_sort_equals_dense_dispatch(rng):
    """Round 3: the sort/segment dispatch must reproduce the one-hot
    formulation EXACTLY — outputs, aux loss, and all grads — including
    under capacity pressure where slot priority decides who drops."""
    T, D, H, E = 64, 8, 12, 4
    params = init_moe_params(jax.random.key(7), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    for k, cf in ((1, 8.0), (2, 8.0), (1, 0.5), (2, 0.4)):
        ys, auxs = moe_apply(params, x, capacity_factor=cf, top_k=k,
                             dispatch_mode="sort")
        yd, auxd = moe_apply(params, x, capacity_factor=cf, top_k=k,
                             dispatch_mode="dense")
        np.testing.assert_allclose(np.asarray(ys), np.asarray(yd),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"k={k} cf={cf}")
        np.testing.assert_allclose(float(auxs), float(auxd), rtol=1e-5)

        def loss(p, mode):
            y, aux = moe_apply(p, x, capacity_factor=cf, top_k=k,
                               dispatch_mode=mode)
            return jnp.sum(y ** 2) + aux

        gs = jax.grad(lambda p: loss(p, "sort"))(params)
        gd = jax.grad(lambda p: loss(p, "dense"))(params)
        for key in ("router", "w1", "w2"):
            np.testing.assert_allclose(
                np.asarray(gs[key]), np.asarray(gd[key]),
                rtol=2e-4, atol=1e-6, err_msg=f"{key} k={k} cf={cf}")


def test_moe_sort_dispatch_memory_scales(rng):
    """The dense (T, K, E, C) slot tensor is O(T^2 K/E) at fixed
    capacity factor; the sort dispatch must not materialize anything
    T x C shaped. Compiled temp memory gap asserts it."""
    T, D, H, E, K = 2048, 32, 64, 8, 2
    params = init_moe_params(jax.random.key(8), E, D, H)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)

    def mem(mode):
        f = jax.jit(lambda p, x: moe_apply(
            p, x, top_k=K, dispatch_mode=mode)[0])
        return f.lower(params, x).compile().memory_analysis() \
            .temp_size_in_bytes

    m_sort, m_dense = mem("sort"), mem("dense")
    # dense slot tensor alone: T*K*E*C*4 = 2048*2*8*640*4 = 84 MB
    assert m_sort * 4 < m_dense, (m_sort, m_dense)


def test_moe_sort_sharded_execution(rng):
    """Sort dispatch under an expert-sharded mesh still produces the
    unsharded result (GSPMD reshards the scatter/gather correctly)."""
    mesh = make_mesh(MeshSpec(data=2, expert=4))
    T, D, H, E = 32, 8, 16, 4
    params = init_moe_params(jax.random.key(9), E, D, H)
    sharded = jax.device_put(params, moe_shardings(params, mesh))
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    y, aux = jax.jit(lambda p, x: moe_apply(
        p, x, top_k=2, dispatch_mode="sort"))(sharded, x)
    ref, _ = moe_apply(jax.tree.map(np.asarray, params), x, top_k=2,
                       dispatch_mode="sort")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# round-5: interleaved (virtual-stage) 1F1B
# ---------------------------------------------------------------------------

def _chain_ref(stage_fn, params, x, y, loss_fn, L, n_mb):
    def f(ws):
        tot = 0.0
        for m in range(n_mb):
            h = x[m]
            for l in range(L):
                h = stage_fn(jax.tree.map(lambda a: a[l], ws), h)
            tot = tot + loss_fn(h, y[m])
        return tot / n_mb
    return jax.value_and_grad(f)(params)


def test_interleaved_1f1b_matches_ad(rng):
    """v virtual chunks per device: loss and per-stage grads exactly
    match AD through the sequential chain, for v in {1, 2, 4} and a
    non-power-of-two v."""
    from veles_tpu.parallel import interleaved_train_step
    S, n_mb, mb, d = 4, 8, 4, 8
    mesh = make_mesh(MeshSpec(pipe=S))
    x = jnp.asarray(rng.standard_normal((n_mb, mb, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((n_mb, mb, d)), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def loss_fn(out, lbl):
        return jnp.mean(jnp.square(out - lbl))

    for v in (1, 2, 3):
        L = v * S
        params = {"w": jnp.asarray(
            rng.standard_normal((L, d, d)) * 0.4, jnp.float32)}
        ref_l, ref_g = _chain_ref(stage_fn, params, x, y, loss_fn,
                                  L, n_mb)
        loss, grads = interleaved_train_step(
            stage_fn, loss_fn, params, x, y, mesh, interleave=v)
        np.testing.assert_allclose(float(loss), float(ref_l),
                                   rtol=2e-6, err_msg=f"v={v}")
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(ref_g["w"]),
                                   rtol=2e-5, atol=2e-6,
                                   err_msg=f"v={v}")


def test_interleaved_1f1b_keyed_aux_and_dp(rng):
    """Keyed mode (per-microbatch fold_in, same derivation as the plain
    schedules) with an aux channel, composed with a data axis."""
    from veles_tpu.parallel import interleaved_train_step
    S, v, n_mb, mb, d = 2, 2, 4, 4, 8
    L = v * S
    mesh = make_mesh(MeshSpec(data=4, pipe=S))
    x = jnp.asarray(rng.standard_normal((n_mb, mb, d)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((n_mb, mb, d)), jnp.float32)
    params = {"w": jnp.asarray(
        rng.standard_normal((L, d, d)) * 0.4, jnp.float32)}
    key = jax.random.key(7)

    def stage_fn(p, h, k):
        # deterministic "aux": mean activation magnitude (so the aux
        # cotangent path is exercised with a checkable reference)
        out = jnp.tanh(h @ p["w"])
        return out, jnp.mean(jnp.abs(out))

    def loss_fn(out, lbl):
        return jnp.mean(jnp.square(out - lbl))

    loss, aux, grads = interleaved_train_step(
        stage_fn, loss_fn, params, x, y, mesh, interleave=v, rng=key,
        with_aux=True)

    # reference: aux joins the loss with weight 1 (the schedule's aux
    # cotangent), averaged over stages... the schedule SUMS stage aux
    # per microbatch then means over microbatches
    def ref(ws):
        tot, taux = 0.0, 0.0
        for m in range(n_mb):
            h = x[m]
            for l in range(L):
                h, a = stage_fn(jax.tree.map(lambda q: q[l], ws), h,
                                None)
                taux = taux + a
            tot = tot + loss_fn(h, y[m])
        return (tot + taux) / n_mb, (tot / n_mb, taux / n_mb)
    (_, (ref_l, ref_aux)), ref_g = jax.value_and_grad(
        ref, has_aux=True)(params)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=2e-6)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_g["w"]),
                               rtol=2e-5, atol=2e-6)


def test_interleaved_rejects_bad_stage_count(rng):
    from veles_tpu.parallel import interleaved_train_step
    mesh = make_mesh(MeshSpec(pipe=4))
    params = {"w": jnp.zeros((6, 8, 8))}  # 6 != 2*4
    x = jnp.zeros((8, 4, 8))
    with pytest.raises(ValueError, match="leading stage axis"):
        interleaved_train_step(lambda p, h: h, lambda o, l: 0.0,
                               params, x, x, mesh, interleave=2)
