"""NN forward-layer units — the Znicz layer library rebuilt TPU-first.

Reference capability checklist (SURVEY.md §2.10; docs
manualrst_veles_algorithms.rst:10-134): fully-connected (all2all with
softmax/tanh/relu/sincos), conv, pooling (max/avg), deconv, depool, dropout,
LRN, plus evaluators (softmax CE, MSE). Kohonen SOM and RBM live in
units/kohonen.py / units/rbm.py (non-SGD custom updates).

Every unit here is a thin declarative wrapper over veles_tpu.ops — pure
functions the Workflow traces into one jitted step. Weights initialize with
the Znicz "smart init" (uniform ±1/sqrt(fan_in)).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..ops.activations import ACTIVATIONS
from .base import Context, Forward, Spec, Unit


def _cast_policy(dtype):
    return None if dtype in (None, "") else jnp.dtype(dtype)


class All2All(Forward):
    """Fully-connected layer (reference Znicz all2all; gemm on the MXU)."""

    def __init__(self, output_size: int, *, activation: str = "linear",
                 weights_scale: float = 1.0, include_bias: bool = True,
                 compute_dtype=None, name=None, inputs=("@input",),
                 per_position: bool = False):
        super().__init__(name, inputs)
        self.output_size = int(output_size)
        self.activation = activation
        self.weights_scale = weights_scale
        self.include_bias = include_bias
        self.compute_dtype = _cast_policy(compute_dtype)
        # per_position: project the TRAILING feature axis only, keeping
        # leading (B, T, ...) dims — e.g. (B, T, E) -> (B, T, V) logits
        # for the sequence evaluator. Default flattens per sample (the
        # reference all2all semantics).
        self.per_position = bool(per_position)

    def _in_features(self, in_spec: Spec) -> int:
        if self.per_position:
            return int(in_spec.shape[-1])
        return int(np.prod(in_spec.shape[1:]))

    def output_spec(self, in_specs):
        s = in_specs[0]
        if self.per_position:
            return Spec(tuple(s.shape[:-1]) + (self.output_size,), s.dtype)
        return Spec((s.shape[0], self.output_size), s.dtype)

    def init(self, key, in_specs):
        fan_in = self._in_features(in_specs[0])
        kw, _ = jax.random.split(key)
        params = {"w": ops.smart_uniform_init(
            kw, (fan_in, self.output_size), fan_in,
            scale=self.weights_scale)}
        if self.include_bias:
            params["b"] = jnp.zeros((self.output_size,), jnp.float32)
        return params, {}

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        if self.per_position:
            lead = x.shape[:-1]
            x = x.reshape(-1, x.shape[-1])
        else:
            lead = None
            x = x.reshape(x.shape[0], -1)
        y = ops.dense(x, params["w"], params.get("b"),
                      compute_dtype=self.compute_dtype)
        if lead is not None:
            y = y.reshape(lead + (self.output_size,))
        return ACTIVATIONS[self.activation](y), state


class All2AllTanh(All2All):
    def __init__(self, output_size, **kw):
        kw.setdefault("activation", "tanh")
        kw.setdefault("weights_scale", 1.0)
        super().__init__(output_size, **kw)


class All2AllRELU(All2All):
    def __init__(self, output_size, **kw):
        kw.setdefault("activation", "relu")
        super().__init__(output_size, **kw)


class All2AllSincos(All2All):
    def __init__(self, output_size, **kw):
        kw.setdefault("activation", "sincos")
        super().__init__(output_size, **kw)


class All2AllSoftmax(All2All):
    """Output layer: emits LOGITS (softmax itself fuses into the CE loss —
    the reference computed softmax in the evaluator's kernel too)."""

    def __init__(self, output_size, **kw):
        kw.setdefault("activation", "linear")
        super().__init__(output_size, **kw)


class Conv(Forward):
    """2-D convolution (NHWC) with optional activation."""

    def __init__(self, n_kernels: int, kx: int = 3, ky: Optional[int] = None,
                 *, stride=1, padding="SAME", activation="linear",
                 weights_scale=1.0, include_bias=True, compute_dtype=None,
                 name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.n_kernels = int(n_kernels)
        self.kx = int(kx)
        self.ky = int(ky if ky is not None else kx)
        self.stride = stride
        self.padding = padding
        self.activation = activation
        self.weights_scale = weights_scale
        self.include_bias = include_bias
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        s = in_specs[0]
        w = Spec((self.ky, self.kx, s.shape[-1], self.n_kernels), s.dtype)
        return jax.eval_shape(
            lambda x, w_: ops.conv2d(x, w_, stride=self.stride,
                                     padding=self.padding), s, w)

    def init(self, key, in_specs):
        cin = in_specs[0].shape[-1]
        fan_in = self.kx * self.ky * cin
        kw, _ = jax.random.split(key)
        params = {"w": ops.smart_uniform_init(
            kw, (self.ky, self.kx, cin, self.n_kernels), fan_in,
            scale=self.weights_scale)}
        if self.include_bias:
            params["b"] = jnp.zeros((self.n_kernels,), jnp.float32)
        return params, {}

    def apply(self, params, state, xs, ctx):
        y = ops.conv2d(xs[0], params["w"], params.get("b"),
                       stride=self.stride, padding=self.padding,
                       compute_dtype=self.compute_dtype)
        return ACTIVATIONS[self.activation](y), state


class ConvRELU(Conv):
    def __init__(self, n_kernels, kx=3, ky=None, **kw):
        kw.setdefault("activation", "relu")
        super().__init__(n_kernels, kx, ky, **kw)


class ConvTanh(Conv):
    def __init__(self, n_kernels, kx=3, ky=None, **kw):
        kw.setdefault("activation", "tanh")
        super().__init__(n_kernels, kx, ky, **kw)


class Deconv(Forward):
    """Transposed convolution (reference Znicz deconv)."""

    def __init__(self, n_kernels: int, kx: int = 3, ky: Optional[int] = None,
                 *, stride=1, padding="SAME", activation="linear",
                 weights_scale=1.0, compute_dtype=None, name=None,
                 inputs=("@input",)):
        super().__init__(name, inputs)
        self.n_kernels = int(n_kernels)
        self.kx = int(kx)
        self.ky = int(ky if ky is not None else kx)
        self.stride = stride
        self.padding = padding
        self.activation = activation
        self.weights_scale = weights_scale
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        s = in_specs[0]
        w = Spec((self.ky, self.kx, s.shape[-1], self.n_kernels), s.dtype)
        return jax.eval_shape(
            lambda x, w_: ops.deconv2d(x, w_, stride=self.stride,
                                       padding=self.padding), s, w)

    def init(self, key, in_specs):
        cin = in_specs[0].shape[-1]
        fan_in = self.kx * self.ky * cin
        kw, _ = jax.random.split(key)
        return {"w": ops.smart_uniform_init(
            kw, (self.ky, self.kx, cin, self.n_kernels), fan_in,
            scale=self.weights_scale),
            "b": jnp.zeros((self.n_kernels,), jnp.float32)}, {}

    def apply(self, params, state, xs, ctx):
        y = ops.deconv2d(xs[0], params["w"], params["b"],
                         stride=self.stride, padding=self.padding,
                         compute_dtype=self.compute_dtype)
        return ACTIVATIONS[self.activation](y), state


class MaxPooling(Unit):
    def __init__(self, window=2, stride=None, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.window = window
        self.stride = stride

    def output_spec(self, in_specs):
        return jax.eval_shape(
            lambda x: ops.max_pool(x, self.window, self.stride), in_specs[0])

    def apply(self, params, state, xs, ctx):
        return ops.max_pool(xs[0], self.window, self.stride), state


class AvgPooling(Unit):
    def __init__(self, window=2, stride=None, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.window = window
        self.stride = stride

    def output_spec(self, in_specs):
        return jax.eval_shape(
            lambda x: ops.avg_pool(x, self.window, self.stride), in_specs[0])

    def apply(self, params, state, xs, ctx):
        return ops.avg_pool(xs[0], self.window, self.stride), state


class StochasticAbsPooling(MaxPooling):
    """Pool by max |x| keeping sign (Znicz's stochastic abs-pooling family;
    deterministic variant used at inference)."""

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        mag = ops.max_pool(jnp.abs(x), self.window, self.stride)
        pos = ops.max_pool(x, self.window, self.stride)
        neg = -ops.max_pool(-x, self.window, self.stride)
        return jnp.where(pos >= mag, pos, neg), state


class Depool(Unit):
    """Unpooling by uniform spread (pairs with Deconv for autoencoders)."""

    def __init__(self, window=2, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.window = window

    def output_spec(self, in_specs):
        return jax.eval_shape(
            lambda x: ops.avg_unpool(x, self.window), in_specs[0])

    def apply(self, params, state, xs, ctx):
        return ops.avg_unpool(xs[0], self.window), state


class Dropout(Unit):
    """Inverted dropout; identity at eval (reference Znicz dropout;
    RNG = jax threefry via ctx.unit_key, replacing ocl/random.cl's
    xorshift1024* states).

    use_pallas: True/False forces a formulation; None follows the
    platform (``ops.use_pallas_default``): the fused kernel on a TPU,
    ``jax.random.bernoulli`` elsewhere.  Nothing is measured: on the
    chip the kernel ties or wins (0.1645 against 0.1651 ms forward +
    backward at 512 x 4096 in one checkout, 0.1145 against 0.176 in
    another; TPU v5 lite, docs/autotune.md)."""

    stochastic = True

    def __init__(self, dropout_ratio=0.5, name=None, inputs=("@input",),
                 use_pallas=None):
        super().__init__(name, inputs)
        self.ratio = float(dropout_ratio)
        self.use_pallas = use_pallas

    def uses_kernel(self) -> bool:
        """The fused Pallas kernel (True) or ``jax.random`` (False): the
        forced choice, else the platform default."""
        return ops.use_pallas_default() if self.use_pallas is None \
            else bool(self.use_pallas)

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        if not ctx.train or self.ratio <= 0.0:
            return x, state
        key = ctx.unit_key(self.name)
        if self.uses_kernel():
            # In-kernel counter-based RNG; mask regenerated in backward
            # (ops/pallas_kernels.py, parity: ocl/random.cl).
            seed = jax.random.bits(key, dtype=jnp.uint32)
            if ctx.mesh is None or ctx.manual_axes is not None \
                    or x.ndim < 2:
                return ops.fused_dropout(x, seed, self.ratio), state
            # under a GSPMD mesh each device runs the kernel on its own
            # batch rows (parallel.mesh.shard_batch); the row offset
            # keeps the mask a function of GLOBAL element indices, so
            # it does not depend on the mesh
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import batch_axes, shard_batch
            axes = batch_axes(ctx.mesh, x.shape[0])

            def local(xl, seed):  # shard-map-root: data,fsdp
                rows = math.prod(xl.shape[:-1])
                off = 0 if axes is None else \
                    jax.lax.axis_index(axes).astype(jnp.uint32) * rows
                return ops.fused_dropout(xl, seed, self.ratio,
                                         row_offset=off)

            spec = P(axes, *(None,) * (x.ndim - 1))
            return shard_batch(local, ctx.mesh, (spec, P()),
                               spec)(x, seed), state
        keep = 1.0 - self.ratio
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state


class LRN(Unit):
    """Local response normalization across channels.

    method: "band", the one formulation (ops/lrn.py: a C x C 0/1 matmul
    at >= HIGH precision).  "auto" is accepted because configuration
    files carry it and means the same; the unit keeps the concrete name,
    so an exported package never carries "auto"."""

    def __init__(self, n=5, k=2.0, alpha=1e-4, beta=0.75, name=None,
                 inputs=("@input",), method="band"):
        super().__init__(name, inputs)
        self.n, self.k, self.alpha, self.beta = n, k, alpha, beta
        self.method = ops.lrn.resolve_method(method)

    def apply(self, params, state, xs, ctx):
        return ops.local_response_norm(
            xs[0], n=self.n, k=self.k, alpha=self.alpha, beta=self.beta,
            method=self.method), state


class MeanDispNormalizer(Unit):
    """(x - mean) * rdisp with dataset statistics stored in unit state
    (reference: veles/mean_disp_normalizer.py:50-138)."""

    def __init__(self, mean=None, rdisp=None, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self._mean = mean
        self._rdisp = rdisp

    def output_spec(self, in_specs):
        return Spec(in_specs[0].shape, jnp.float32)

    def init(self, key, in_specs):
        shape = in_specs[0].shape[1:]
        mean = jnp.asarray(self._mean, jnp.float32) if self._mean is not None \
            else jnp.zeros(shape, jnp.float32)
        rdisp = jnp.asarray(self._rdisp, jnp.float32) \
            if self._rdisp is not None else jnp.ones(shape, jnp.float32)
        return {}, {"mean": mean, "rdisp": rdisp}

    def apply(self, params, state, xs, ctx):
        return ops.mean_disp_normalize(
            xs[0], state["mean"], state["rdisp"]), state


class Flatten(Unit):
    def output_spec(self, in_specs):
        s = in_specs[0]
        return Spec((s.shape[0], int(np.prod(s.shape[1:]))), s.dtype)

    def apply(self, params, state, xs, ctx):
        return xs[0].reshape(xs[0].shape[0], -1), state


class LayerNorm(Unit):
    """Layer normalization over the trailing feature axis with learnable
    scale/shift — the standard companion of the attention stack (no
    reference analog; LRN is the reference's only normalizer)."""

    def __init__(self, eps: float = 1e-5, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.eps = float(eps)

    def output_spec(self, in_specs):
        return in_specs[0]

    def init(self, key, in_specs):
        d = in_specs[0].shape[-1]
        return {"scale": jnp.ones((d,)), "shift": jnp.zeros((d,))}, {}

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        out = y * params["scale"] + params["shift"]
        return out.astype(x.dtype), state


class FFN(Unit):
    """Per-position two-layer MLP with residual — the transformer block's
    FFN half (y = x + W2·act(W1·x)); pairs with the attention unit the
    way MoEFFN does for the sparse case. No reference analog (the
    reference has no sequence models — SURVEY.md §5.7)."""

    def __init__(self, d_hidden: int, activation: str = "relu",
                 residual: bool = True, name=None, inputs=("@input",),
                 compute_dtype=None):
        super().__init__(name, inputs)
        self.d_hidden = int(d_hidden)
        self.activation = activation
        self.residual = bool(residual)
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        return in_specs[0]

    def init(self, key, in_specs):
        E = in_specs[0].shape[-1]
        k1, k2 = jax.random.split(key)
        return {"w1": ops.smart_uniform_init(k1, (E, self.d_hidden), E),
                "b1": jnp.zeros((self.d_hidden,), jnp.float32),
                "w2": ops.smart_uniform_init(k2, (self.d_hidden, E),
                                             self.d_hidden),
                "b2": jnp.zeros((E,), jnp.float32)}, {}

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        h = ops.dense(flat, params["w1"], params["b1"],
                      compute_dtype=self.compute_dtype)
        h = ACTIVATIONS[self.activation](h)
        y = ops.dense(h, params["w2"], params["b2"],
                      compute_dtype=self.compute_dtype)
        y = y.reshape(lead + (x.shape[-1],))
        if self.residual:
            y = y + x
        return y.astype(x.dtype), state


def rms_normalize(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the trailing axis, in
    float32, returned in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


class RMSNorm(Unit):
    """Root-mean-square normalisation over the trailing feature axis with
    a learnable scale and no shift.  With a second input the normalised
    first is added to it, ``y = xs[1] + RMS(xs[0])``: the residual stream
    of a block wired ``h + N(f(N(h)))``, written in a layer list as
    ``{"type": "rms_norm", "inputs": [f, h]}``."""

    def __init__(self, eps: float = 1e-5, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.eps = float(eps)

    def output_spec(self, in_specs):
        return in_specs[-1]

    def init(self, key, in_specs):
        return {"scale": jnp.ones((in_specs[0].shape[-1],))}, {}

    def apply(self, params, state, xs, ctx):
        y = rms_normalize(xs[0], params["scale"], self.eps)
        if len(xs) > 1:
            y = xs[1] + y.astype(xs[1].dtype)
        return y, state


class Add(Unit):
    """The sum of its inputs: how a layer list writes a block wired
    ``h + f(N(h))``, ``{"type": "add", "inputs": [f, h]}``, whatever the
    mixer ``f`` is.  The sum takes the last input's dtype (the residual
    stream's)."""

    def output_spec(self, in_specs):
        return in_specs[-1]

    def apply(self, params, state, xs, ctx):
        y = xs[-1]
        for x in xs[:-1]:
            y = y + x.astype(y.dtype)
        return y, state


class GatedMLP(Unit):
    """Per-position gated MLP, ``y = Wd(act(Wg x) * (Wu x))`` (SwiGLU
    with ``activation="silu"``): three matrices, no bias, no residual."""

    def __init__(self, d_hidden: int, activation: str = "silu", name=None,
                 inputs=("@input",), compute_dtype=None):
        super().__init__(name, inputs)
        self.d_hidden = int(d_hidden)
        self.activation = activation
        self.compute_dtype = _cast_policy(compute_dtype)

    def output_spec(self, in_specs):
        return in_specs[0]

    def init(self, key, in_specs):
        E = in_specs[0].shape[-1]
        kg, ku, kd = jax.random.split(key, 3)
        return {"wg": ops.smart_uniform_init(kg, (E, self.d_hidden), E),
                "wu": ops.smart_uniform_init(ku, (E, self.d_hidden), E),
                "wd": ops.smart_uniform_init(kd, (self.d_hidden, E),
                                             self.d_hidden)}, {}

    def apply(self, params, state, xs, ctx):
        x = xs[0]
        y = gated_mlp(x.reshape(-1, x.shape[-1]), params["wg"],
                      params["wu"], params["wd"], self.activation,
                      self.compute_dtype)
        return y.reshape(x.shape).astype(x.dtype), state


def gated_mlp(x, wg, wu, wd, activation="silu", compute_dtype=None):
    """``(act(x wg) * (x wu)) wd`` on (rows, E), float32 accumulation;
    ``wg`` None: no gate, ``act(x wu) wd``."""
    act = ACTIVATIONS[activation]
    g = None if wg is None \
        else ops.dense(x, wg, compute_dtype=compute_dtype)
    u = ops.dense(x, wu, compute_dtype=compute_dtype)
    h = act(u) if g is None else act(g) * u
    return ops.dense(h, wd, compute_dtype=compute_dtype)


class Embedding(Unit):
    """Token embedding: int tokens (B, T) -> (B, T, dim) by table lookup.

    The front door of the sequence/long-context model family (the
    reference had no sequence models in core — SURVEY.md §5.7); float
    inputs from generic loaders are cast to int32 indices."""

    def __init__(self, vocab: int, dim: int, name=None,
                 inputs=("@input",), scale: Optional[float] = None):
        super().__init__(name, inputs)
        self.vocab = int(vocab)
        self.dim = int(dim)
        # rows leave multiplied by this (sqrt(dim) in models that scale
        # the embedding to the width of the residual stream)
        self.scale = None if scale is None else float(scale)

    def output_spec(self, in_specs):
        s = in_specs[0]
        return Spec(tuple(s.shape) + (self.dim,), jnp.float32)

    def init(self, key, in_specs):
        return {"table": ops.smart_uniform_init(
            key, (self.vocab, self.dim), self.vocab)}, {}

    def apply(self, params, state, xs, ctx):
        idx = xs[0].astype(jnp.int32)
        rows = jnp.take(params["table"], idx, axis=0)
        if self.scale is not None:
            rows = rows * self.scale
        return rows, state


def input_vocab(workflow, params) -> Optional[int]:
    """Embedding-table rows of the chain's front (None without an
    Embedding) — THE bound on acceptable input token ids, shared by the
    REST /predict out-of-vocab 400 guard (restful._vocab_size) and the
    compiled-artifact export's sealed ``input_vocab`` so the two can
    never drift."""
    for u in workflow.topo_order():
        if isinstance(u, Embedding):
            return int(np.shape(params[u.name]["table"])[0])
    return None


class SeqLast(Unit):
    """(B, T, ...) -> (B, ...): the final time step (e.g. next-token
    readout after causal attention)."""

    def output_spec(self, in_specs):
        s = in_specs[0]
        return Spec((s.shape[0],) + tuple(s.shape[2:]), s.dtype)

    def apply(self, params, state, xs, ctx):
        return xs[0][:, -1], state


class Reshape(Unit):
    """Reshape the per-sample trailing dims (e.g. flat 784 -> 28x28x1 for a
    conv trunk fed by a vector loader)."""

    def __init__(self, shape, name=None, inputs=("@input",)):
        super().__init__(name, inputs)
        self.shape = tuple(int(s) for s in shape)

    def output_spec(self, in_specs):
        s = in_specs[0]
        if int(np.prod(s.shape[1:])) != int(np.prod(self.shape)):
            raise ValueError(
                f"cannot reshape {s.shape[1:]} to {self.shape}")
        return Spec((s.shape[0],) + self.shape, s.dtype)

    def apply(self, params, state, xs, ctx):
        return xs[0].reshape((xs[0].shape[0],) + self.shape), state


# -- evaluators (loss units) -------------------------------------------------

class Evaluator(Unit):
    """Base loss unit: consumes (output, labels/targets); its output is the
    scalar loss; metrics are returned via state-free aux (collected by the
    Workflow). Reference: Znicz evaluator units feeding Decision.

    A subclass defines ``evaluate``: one pass over its inputs that yields
    the step's metrics, ``"loss"`` among them.  ``apply`` and ``metrics``
    are views of it, and ``Workflow.forward`` calls it once a step and
    keeps both."""

    is_evaluator = True

    def evaluate(self, params, state, xs, ctx) -> dict:
        raise NotImplementedError

    def apply(self, params, state, xs, ctx):
        return self.evaluate(params, state, xs, ctx)["loss"], state

    def metrics(self, params, state, xs, ctx) -> dict:
        return self.evaluate(params, state, xs, ctx)


class EvaluatorSoftmax(Evaluator):
    """Softmax cross-entropy over logits + integer labels
    (reference 'evaluator' for classification). An optional third input
    "@mask" (loader-provided, 1.0 per real sample) keeps metrics exact with
    padded fixed-shape batches.

    Sequence form: logits (B, T, V) with labels (B, T) compute the
    per-position loss (next-token LM training); the per-sample mask
    broadcasts across positions and metrics count positions.

    Large logits on a TPU go through the loss in one sweep
    (``ops.losses.softmax_loss_path``); which path a call took is the
    gauge ``vt_softmax_loss_path{unit, path}``, set when traced."""

    def __init__(self, name=None, inputs=("@input", "@labels", "@mask")):
        super().__init__(name, inputs)

    def output_spec(self, in_specs):
        return Spec((), jnp.float32)

    @staticmethod
    def _mask(xs):
        m = xs[2] if len(xs) > 2 else None
        labels = xs[1]
        if m is not None and m.ndim < labels.ndim:
            m = jnp.broadcast_to(
                m.reshape(m.shape + (1,) * (labels.ndim - m.ndim)),
                labels.shape)
        return m

    def _note_path(self, path):
        from ..runtime.metrics import registry
        gauge = registry().gauge(
            "vt_softmax_loss_path",
            "1 on the path the softmax loss took when last traced",
            labels=("unit", "path"))
        for p in ("swept", "plain"):
            gauge.labels(unit=self.name, path=p).set(float(p == path))

    def evaluate(self, params, state, xs, ctx):
        mask = self._mask(xs)
        # inside a schedule's shard_map the call is already per shard
        mesh = None if ctx is None or ctx.manual_axes is not None \
            else ctx.mesh
        self._note_path(ops.losses.softmax_loss_path(xs[0].shape, mesh))
        loss, n_err = ops.softmax_cross_entropy(xs[0], xs[1], mask=mask,
                                                mesh=mesh)
        n = mask.sum() if mask is not None else jnp.asarray(
            float(np.prod(xs[1].shape)), jnp.float32)
        return {"loss": loss, "n_err": n_err, "n_samples": n}


class EvaluatorMSE(Evaluator):
    """MSE against targets (reference MSE evaluator / autoencoder path)."""

    def __init__(self, name=None, inputs=("@input", "@targets", "@mask")):
        super().__init__(name, inputs)

    def output_spec(self, in_specs):
        return Spec((), jnp.float32)

    @staticmethod
    def _mask(xs):
        return xs[2] if len(xs) > 2 else None

    def evaluate(self, params, state, xs, ctx):
        mask = self._mask(xs)
        loss, agg = ops.mse_loss(xs[0], xs[1], mask=mask)
        n = mask.sum() if mask is not None else jnp.asarray(
            xs[0].shape[0], jnp.float32)
        return {"loss": loss, "mse_sum": agg, "n_samples": n}
