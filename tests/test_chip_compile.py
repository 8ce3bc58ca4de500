"""The Pallas kernels of the main path, compiled for a TPU v5e that is
described and not attached (the chip's own compiler is installed here).

Interpret mode — what the rest of the suite runs the kernels in — passes
where the real lowering refuses a program: a tile that is not aligned, too
much VMEM, a kernel that cannot be partitioned over a mesh.  These cases
compile each kernel at the shapes ``chip_smoke.py`` and the benchmark run on
the chip, on one described chip, and the kernel call sites of the units
(attention, dropout, the softmax evaluator) inside a jitted function over a
4-device mesh of described chips with batch-sharded operands.  The loader's
gather is a jit of its own on one device (loader/fullbatch.py); no mesh
reaches it.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a fixture, in this one file, never at
import: only one process at a time may hold the TPU library, and under
pytest-xdist every worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from veles_tpu import ops
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.parallel.mesh import MeshSpec, make_mesh
from veles_tpu.units.base import Context, Spec
from veles_tpu.units.nn import Dropout, EvaluatorSoftmax
from veles_tpu.units.parallel_nn import MultiHeadAttention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without the chip: keep
    # the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def for_the_chip(monkeypatch):
    """Code that asks the backend still sees the CPU here and would pick
    interpret mode: steer the one policy function, in the test."""
    monkeypatch.setattr(ops, "use_pallas_default", lambda platform=None: True)
    monkeypatch.setattr(pk, "use_pallas_default", lambda platform=None: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _custom_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _value_and_grads(f):
    def g(*args):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
            argnums=tuple(range(len(args))))(*args)
    return g


def _flash(B, T, H, Hk, D, window=None, grad=True, blocks=(256, 1024)):
    def case(sh):
        f = lambda q, k, v: pk.flash_attention(  # noqa: E731
            q, k, v, True, None, *blocks, window=window)
        return (_value_and_grads(f) if grad else f,
                [_sds((B, T, H, D), "bfloat16", sh),
                 _sds((B, T, Hk, D), "bfloat16", sh),
                 _sds((B, T, Hk, D), "bfloat16", sh)],
                3 if grad else 1)
    return case


def _paged(B, H, Hk, D, dtype, psz=16, n_ptab=128):
    def case(sh):
        rows = B * n_ptab + 1
        return (lambda q, k, v, ptab, pos: pk.paged_attention_decode(
                    q, k, v, ptab, pos, page_size=psz, n_kv_heads=Hk),
                [_sds((B, H, D), "float32", sh),
                 _sds((rows, psz, Hk, D), dtype, sh),
                 _sds((rows, psz, Hk, D), dtype, sh),
                 _sds((B, n_ptab), "int32", sh), _sds((B,), "int32", sh)],
                1)
    return case


def _dropout(shape, dtype, rate):
    def case(sh):
        return (lambda x, s: _value_and_grads(
                    lambda x: pk.fused_dropout(x, s, rate))(x),
                [_sds(shape, dtype, sh), _sds((), "uint32", sh)], 2)
    return case


def _gather(n, f, m):
    def case(sh):
        return (lambda d, i: pk.gather_rows(d, i),
                [_sds((n, f), "float32", sh), _sds((m,), "int32", sh)], 1)
    return case


def _grouped(rows, groups, k, n, grad=True):
    """The ragged grouped product of the routed experts: a buffer of
    ``rows`` (every route may land here) in tiles of 128."""
    def case(sh):
        f = lambda lhs, rhs, sizes: pk.grouped_matmul(  # noqa: E731
            lhs, rhs, sizes, 128, 512)
        if grad:
            f = lambda lhs, rhs, sizes, _f=f: _value_and_grads(  # noqa: E731
                lambda a, b: _f(a, b, sizes))(lhs, rhs)
        return (f, [_sds((rows, k), "bfloat16", sh),
                    _sds((groups, k, n), "bfloat16", sh),
                    _sds((groups,), "int32", sh)], 3 if grad else 1)
    return case


def _row_sum(rows, width, tokens=4096):
    """The routed experts' combine and dispatch gradient: a buffer of
    ``rows`` summed by token into (tokens, width) float32 that stays in
    VMEM (32 MiB at 2048 columns, 42 MiB at 2688)."""
    def case(sh):
        return (lambda r, t, w: pk.sum_rows_by_token(r, t, tokens, w),
                [_sds((rows, width), "bfloat16", sh),
                 _sds((rows,), "int32", sh), _sds((rows,), "float32", sh)],
                1)
    return case


def _xent(rows, classes, dtype="float32"):
    """The loss's forward sweep over (rows, classes) logits, with the
    loss, the predictions and the logits' gradient around it."""
    def case(sh):
        def f(x, labels):
            return jax.value_and_grad(
                lambda x: pk.softmax_xent_rows(x, labels)[0].mean())(x)
        return (f, [_sds((rows, classes), dtype, sh),
                    _sds((rows,), "int32", sh)], 1)
    return case


def _flash_gqa_d128(window, **kw):
    return _flash(1, 4096, 32, 4, 128, window=window, **kw)


def _flash_opt(B, T, blocks):
    """The OPT cells' attention, two D = 64 heads a grid step out of
    (B, T, 1024), at a block shape the probe measures."""
    assert pk.flash_heads_per_step((B, T, 16, 64), (B, T, 16, 64),
                                   *blocks) == 2
    return _flash(B, T, 16, 16, 64, blocks=blocks)


#: name -> builder(sharding) -> (function, argument shapes, kernels expected)
ONE_CHIP = {
    "grouped_matmul_fwd_up_16x2048x1024": _grouped(
        34816, 16, 2048, 1024, grad=False),
    "grouped_matmul_fwd_bwd_up_16x2048x1024": _grouped(
        34816, 16, 2048, 1024),
    "grouped_matmul_fwd_bwd_down_16x1024x2048": _grouped(
        34816, 16, 1024, 2048),
    # widths that 512-column blocks do not divide: 1856 = 14.5 x 128 goes
    # whole, 2688 = 21 x 128 in blocks of 384
    "grouped_matmul_fwd_bwd_up_8x2688x1856": _grouped(5632, 8, 2688, 1856),
    "grouped_matmul_fwd_bwd_down_8x1856x2688": _grouped(
        5632, 8, 1856, 2688),
    "grouped_matmul_fwd_bwd_up_large_8x2688x1856": _grouped(
        25600, 8, 2688, 1856),
    "sum_rows_by_token_trinity_small_14336x2048": _row_sum(14336, 2048),
    "sum_rows_by_token_trinity_large_34816x2048": _row_sum(34816, 2048),
    "sum_rows_by_token_hybrid_small_5632x2688": _row_sum(5632, 2688),
    "sum_rows_by_token_hybrid_large_25600x2688": _row_sum(25600, 2688),
    "flash_fwd_bwd_t4096_h32_kv2_d128_full": _flash(1, 4096, 32, 2, 128),
    # the delta-rule hybrid's full layer: a share of 15 heads, no groups
    "flash_fwd_bwd_t4096_h15_d128_full": _flash(1, 4096, 15, 15, 128),
    # the latent attention of the Kimi share: keys 192 wide, values padded
    # to them
    "flash_fwd_bwd_t4096_h32_d192_full": _flash(1, 4096, 32, 32, 192),
    "sum_rows_by_token_kimi_small_4096x2304": _row_sum(4096, 2304),
    "sum_rows_by_token_kimi_large_33792x2304": _row_sum(33792, 2304),
    "flash_fwd_bwd_t4096_h32_kv4_d128_window2048": _flash_gqa_d128(2048),
    "flash_fwd_bwd_t4096_h32_kv4_d128_full": _flash_gqa_d128(None),
    "flash_fwd_bwd_trinity_window_512x512": _flash_gqa_d128(
        2048, blocks=(512, 512)),
    "flash_fwd_bwd_trinity_full_1024x1024": _flash_gqa_d128(
        None, blocks=(1024, 1024)),
    "flash_fwd_bwd_opt_t2048_256x1024": _flash_opt(4, 2048, (256, 1024)),
    "flash_fwd_bwd_opt_t2048_512x512": _flash_opt(4, 2048, (512, 512)),
    "flash_fwd_bwd_opt_t2048_1024x512": _flash_opt(4, 2048, (1024, 512)),
    "flash_fwd_bwd_opt_t2048_1024x1024": _flash_opt(4, 2048, (1024, 1024)),
    "flash_fwd_bwd_opt_t512_512x512": _flash_opt(16, 512, (512, 512)),
    "flash_fwd_bwd_transposed_d80": _flash(2, 1024, 8, 8, 80),
    "flash_fwd_b16_t2048": _flash(16, 2048, 8, 8, 64, grad=False),
    "flash_fwd_bwd_b16_t2048": _flash(16, 2048, 8, 8, 64),
    "flash_fwd_bwd_t4096_d128": _flash(1, 4096, 8, 8, 128),
    "flash_window_gqa_t8192": _flash(1, 8192, 8, 2, 64, window=1024),
    "paged_decode_f32_b32_h16_d128": _paged(32, 16, 16, 128, "float32"),
    "paged_decode_bf16_gqa": _paged(32, 16, 4, 128, "bfloat16"),
    "paged_decode_serve_geometry": _paged(8, 8, 8, 64, "float32"),
    "fused_dropout_f32_4096sq": _dropout((4096, 4096), "float32", 0.3),
    "fused_dropout_bf16_alexnet_fc": _dropout((512, 4096), "bfloat16", 0.5),
    "gather_rows_60000x784": _gather(60000, 784, 512),
    "softmax_xent_opt_8192x50272": _xent(8192, 50272),
    "softmax_xent_trinity_4096x25024": _xent(4096, 25024),
    "softmax_xent_hybrid_share_4096x12544": _xent(4096, 12544),
    "softmax_xent_bf16_8192x50272": _xent(8192, 50272, "bfloat16"),
}


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_kernel_compiles_for_one_chip(topo, for_the_chip, name):
    fn, args, kernels = ONE_CHIP[name](SingleDeviceSharding(topo.devices[0]))
    assert _custom_calls(fn, *args) == kernels


def _attention_site(mesh, batch_sh):
    u = MultiHeadAttention(8, name="attn", rope=True, residual=True,
                           use_flash=True)
    spec = Spec((16, 2048, 512), jnp.bfloat16)
    params, _ = jax.eval_shape(lambda k: u.init(k, [spec]),
                               jax.random.key(0))

    def f(params, x):
        ctx = Context(train=True, key=None, mesh=mesh)
        return u.apply(params, {}, [x], ctx)[0]

    rep = NamedSharding(mesh, P())
    return (_value_and_grads(f),
            [jax.tree.map(lambda s: _sds(s.shape, s.dtype, rep), params),
             _sds(spec.shape, spec.dtype, batch_sh)], 3)


def _attention_gqa_site(mesh, batch_sh):
    """Grouped queries at D = 128 with a window, QK-norm and a gate, as
    the Trinity cell's sliding layers: one head a grid step over
    transposed copies, K/V's index map names the shared kv head's row
    (under ``model=2`` each device holds 4 q heads and 1 kv head)."""
    u = MultiHeadAttention(8, head_dim=128, n_kv_heads=2, name="attn",
                           rope=True, window=1024, qk_norm=True, gate=True,
                           use_flash=True)
    spec = Spec((8, 2048, 512), jnp.bfloat16)
    params, _ = jax.eval_shape(lambda k: u.init(k, [spec]),
                               jax.random.key(0))

    def f(params, x):
        ctx = Context(train=True, key=None, mesh=mesh)
        return u.apply(params, {}, [x], ctx)[0]

    rep = NamedSharding(mesh, P())
    return (_value_and_grads(f),
            [jax.tree.map(lambda s: _sds(s.shape, s.dtype, rep), params),
             _sds(spec.shape, spec.dtype, batch_sh)], 3)


def _dropout_site(mesh, batch_sh):
    u = Dropout(0.5, name="drop", use_pallas=True)

    def f(x, key):
        ctx = Context(train=True, key=key, mesh=mesh)
        return _value_and_grads(
            lambda x: u.apply({}, {}, [x], ctx)[0])(x)

    return (f, [_sds((512, 4096), "bfloat16", batch_sh),
                jax.eval_shape(lambda: jax.random.key(0))], 2)


def _evaluator_site(mesh, batch_sh):
    """The evaluator over an LM's logits with batch-sharded rows: each
    device sweeps its own 4 x 2048 rows of the 50,272 classes."""
    u = EvaluatorSoftmax(name="evaluator")
    assert ops.losses.softmax_loss_path((16, 2048, 50272), mesh) == "swept"

    def f(x, labels, mask):
        ctx = Context(train=True, key=None, mesh=mesh)
        return jax.value_and_grad(
            lambda x: u.apply({}, {}, [x, labels, mask], ctx)[0])(x)

    return (f, [_sds((16, 2048, 50272), "float32", batch_sh),
                _sds((16, 2048), "int32", batch_sh),
                _sds((16,), "float32", batch_sh)], 1)


#: name -> (mesh spec, builder(mesh, batch sharding))
CALL_SITES = {
    "attention_data4": (MeshSpec(data=4), _attention_site),
    "attention_data2_fsdp2": (MeshSpec(data=2, fsdp=2), _attention_site),
    "attention_data2_model2": (MeshSpec(data=2, model=2), _attention_site),
    "attention_gqa_data4": (MeshSpec(data=4), _attention_gqa_site),
    "attention_gqa_data2_model2": (MeshSpec(data=2, model=2),
                                   _attention_gqa_site),
    "dropout_data4": (MeshSpec(data=4), _dropout_site),
    "dropout_data2_fsdp2": (MeshSpec(data=2, fsdp=2), _dropout_site),
    "evaluator_data4": (MeshSpec(data=4), _evaluator_site),
    "evaluator_data2_fsdp2": (MeshSpec(data=2, fsdp=2), _evaluator_site),
}


@pytest.mark.parametrize("name", sorted(CALL_SITES))
def test_kernel_call_site_compiles_under_a_mesh(topo, for_the_chip, name):
    """The case the bare kernel call fails: inside a GSPMD-partitioned jit
    XLA refuses it ("Mosaic kernels cannot be automatically partitioned");
    the units hand it a shard_map over the batch (and head) axes."""
    mesh_spec, site = CALL_SITES[name]
    mesh = make_mesh(mesh_spec, devices=topo.devices)
    dp = tuple(a for a in ("data", "fsdp") if mesh.shape[a] > 1)
    fn, args, kernels = site(mesh, NamedSharding(mesh, P(dp)))
    assert _custom_calls(fn, *args) == kernels


# -- a routed layer's forward and backward -------------------------------------

#: cell -> (tokens, width, expert width, top_k, held of 128, gated, act)
ROUTED_LAYERS = {
    "trinity": (4096, 2048, 1024, 8, 16, True, "silu"),
    "hybrid": (4096, 2688, 1856, 6, 8, False, "relu2"),
}


@pytest.mark.parametrize("cell", sorted(ROUTED_LAYERS))
def test_routed_layer_holds_no_array_of_all_routes_rows(topo, for_the_chip,
                                                        cell):
    """Two equal routed layers, forward and backward, at a sparse cell's
    shapes: lowered, the row-summing kernel is there once for each of the
    two buffers however many calls name it (layers, the recomputed
    forward, the dispatch's gradient: PERF.md section 6, PR 33's rule);
    compiled for one described chip, no operation's result has T * K
    rows (or T by K) by D columns, whatever its type.  The plain path
    gathers such arrays."""
    import re
    from veles_tpu.parallel import moe
    T, D, H, K, held, gated, act = ROUTED_LAYERS[cell]
    sh = SingleDeviceSharding(topo.devices[0])
    params = {"router": _sds((D, 128), "float32", sh),
              "wu": _sds((held, D, H), "float32", sh),
              "wd": _sds((held, H, D), "float32", sh)}
    if gated:
        params["wg"] = params["wu"]

    def layers(use_pallas):
        def f(params, x):
            for _ in range(2):
                x = x + moe.routed_experts_apply(
                    params, x, top_k=K, n_held=held, route_scale=2.5,
                    compute_dtype=jnp.bfloat16, use_pallas=use_pallas,
                    activation=act)[0]
            return jnp.sum(x)
        return jax.jit(jax.grad(f, argnums=(0, 1))).trace(
            params, _sds((T, D), "float32", sh))

    all_routes = rf"(\[|<)({T * K}[,x]{D}|{T}[,x]{K}[,x]{D})(\]|x)"
    plain = layers(False).lower(lowering_platforms=("tpu",)).as_text()
    assert re.search(all_routes, plain)
    lowered = layers(True).lower()
    text = lowered.as_text()
    assert text.count('kernel_name = "sum_rows_by_token"') == 2
    assert not re.search(all_routes, text)
    compiled = lowered.compile().as_text()
    assert "sum_rows_by_token" in compiled
    found = re.search(all_routes, compiled)
    assert not found, compiled[found.start() - 200:found.end() + 200]


# -- the LM's loss in the compiled train step ---------------------------------

def _entry_results(text):
    """(result type, opcode and operands) of each instruction of the
    compiled module's entry computation."""
    body = text[text.index("\nENTRY "):]
    out = []
    for line in body[:body.index("\n}")].splitlines()[2:]:
        rhs = line.split(" = ", 1)[1]
        cut = rhs.index(") ") + 1 if rhs.startswith("(") else rhs.index(" ")
        out.append((rhs[:cut], rhs[cut + 1:]))
    return out


def test_lm_step_holds_the_logits_once(topo, for_the_chip, monkeypatch):
    """A small LM's train step over 2048 x 50,272 logits (412 MB),
    compiled for one described chip: ``apply`` and ``metrics`` both ask,
    the step has one forward sweep, and the one array of the logits'
    size that any operation writes is the logits themselves.  Their
    gradient is computed inside the operations that read it (the head's
    two backward products and the bias sum).  The plain formulation,
    forced on the same step, writes several."""
    import re
    from veles_tpu.ops import losses
    from veles_tpu.ops.optimizers import SGD
    from veles_tpu.units.nn import All2All, Embedding
    from veles_tpu.units.workflow import Workflow
    b, t, d, v = 4, 512, 256, 50272
    sh = SingleDeviceSharding(topo.devices[0])
    logits_sized = re.compile(
        rf"f32\[({b},{t},{v}|{b * t},{v}|{v},{b * t})\]")

    def compiled_text():
        wf = Workflow("lm")
        wf.add(Embedding(v, d, name="emb", inputs=("@input",)))
        wf.add(All2All(v, per_position=True, compute_dtype="bfloat16",
                       name="head", inputs=("emb",)))
        wf.add(EvaluatorSoftmax(name="ev",
                                inputs=("head", "@labels", "@mask")))
        specs = {"@input": Spec((b, t), jnp.int32),
                 "@labels": Spec((b, t), jnp.int32),
                 "@mask": Spec((b,), jnp.float32)}
        wf.build(specs)
        opt = SGD(lr=0.1)
        ws = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(lambda k: wf.init_state(k, opt),
                           jax.random.key(0)))
        batch = {k: _sds(s.shape, s.dtype, sh) for k, s in specs.items()}
        return wf.make_train_step(opt).lower(ws, batch).compile().as_text()

    def writers(text):
        return [op for result, op in _entry_results(text)
                if logits_sized.search(result) and not op.startswith(
                    ("bitcast(", "get-tuple-element(", "parameter("))]

    text = compiled_text()
    assert text.count("tpu_custom_call") == 1 and "softmax_xent_fwd" in text
    assert len(writers(text)) == 1, writers(text)
    monkeypatch.setattr(losses, "SWEPT_MIN_BYTES", 1 << 60)
    plain = compiled_text()
    assert "tpu_custom_call" not in plain and len(writers(plain)) > 1
