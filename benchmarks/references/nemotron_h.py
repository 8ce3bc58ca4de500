"""The Nemotron-H hybrid decoder (NVIDIA Nemotron 3 Nano family; Hugging
Face ``transformers`` ``models/nemotron_h/modeling_nemotron_h.py``) in
plain float32 ``jax.numpy``, one chip's share of each layer.  A block is
ONE mixer, ``h <- h + mixer(RMS(h))``; the mixers:

    Mamba-2   [z | xBC | dt] = x W_in
              xBC = silu(conv1d_causal_depthwise(xBC) + b)   kernel K: token t sees t-K+1..t
              [x' | B | C] = xBC;  dt = softplus(dt + dt_bias + dt_origin);  A = -exp(A_log)
              per head, with its group's B_t, C_t, TOKEN BY TOKEN:
                  S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T        (P x N), S_0 = 0
                  y_t = S_t C_t + D x'_t
              out = (RMS_grouped(y * silu(z)) * gate_norm) W_out     groups of inner / G channels
    attention q = x Wq, k = x Wk, v = x Wv per head (grouped keys and values), no positions,
              key j visible to query i iff j <= i;  out = softmax(q k^T / sqrt(d)) v Wo
    experts   s = sigmoid(x Wr) in float32;  top_k of (s + b), b = 0
              w = s[top_k] / (sum + 1e-20) * route_scale
              out = shared(x) + sum_{e in top_k} w_e expert_e(x);  expert(x) = Wd relu(Wu x)^2
              a route to an expert that is not held adds nothing
    logits = RMS(h; final) W_head

The state-space layer is the recurrence itself, not the chunked form the
program computes: an outer scan over blocks of tokens under
``jax.checkpoint`` and an inner scan over a block's tokens, which is a
device for the gradient's memory and the same formula.  The router's
product and scores, and the recurrence, stay float32 whatever the cast.
The router (``route``: sigmoid scores, the choice, the normalised and
scaled weights) and the rows are ``references/afmoe.py``'s, the same
functions.  Imports nothing of the program.  Reads the layer list of the
configuration's file (a layer's ``inputs`` name its sources, by default
the layer before; an ``add`` sums them).  Each layer is rematerialised in
the backward pass and attention goes by blocks of queries.

``leave_out`` plants what the check must catch: ``"ssm_carry"`` (the
state set to zero before every ``chunk``-th token, so nothing crosses a
chunk's boundary), ``"conv"`` (the convolution skipped: ``xBC = silu(xBC
+ b)``) and ``"routed_experts"`` (the routed experts' sum left out, the
shared expert kept).  Any other name is another reference's and changes
nothing here.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.afmoe import build_rows, route  # noqa: F401
from references.train_steps import cross_entropy_sum

#: queries taken at a time against all the keys
QUERY_BLOCK = 512
#: tokens of the recurrence rematerialised together
TOKEN_BLOCK = 64


def _product(cast, spec, a, b):
    return cast.result(jnp.einsum(spec, cast.operand(a), cast.operand(b)))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def recurrence(x, dt, A, B, C, D, reset_every=None):
    """``y`` (b, t, h, p) of the recurrence above; x (b, t, h, p), dt (b,
    t, h), A and D (h,), B and C (b, t, g, n).  ``reset_every``: the
    planted fault.  No ``cast``: the recurrence is elementwise arithmetic
    and a sum over N, not one of the matrix products a precision is put
    on; and the gradient that comes back into y has passed the gated
    norm's division by y's small RMS and lies beyond float8's largest
    number, so a control rounded here would read not-a-number, not a
    gap."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(S, a):
        at, xt, dtt, Bt, Ct = a
        if reset_every:
            S = jnp.where(at % reset_every == 0, 0.0, S)
        xt, dtt = xt.reshape(b, g, r, p), dtt.reshape(b, g, r)
        S = jnp.exp(dtt * A.reshape(g, r))[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, None, :]
        return S, jnp.einsum("bgrpn,bgn->bgrp", S, Ct).reshape(b, h, p)

    @jax.checkpoint
    def some_tokens(S, a):
        return jax.lax.scan(token, S, a)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (t // block, block) + a.shape[:1] + a.shape[2:])
    at = jnp.arange(t).reshape(t // block, block)
    _, y = jax.lax.scan(some_tokens, jnp.zeros((b, g, r, p, n), x.dtype),
                        (at,) + tuple(by_block(a) for a in (x, dt, B, C)))
    y = jnp.moveaxis(y.reshape(t, b, h, p), 0, 1)
    return y + D[:, None] * x


def _mamba2(layer, p, x, cast, leave_out):
    b, t, _ = x.shape
    h, hd = int(layer["n_heads"]), int(layer["head_dim"])
    g, n = int(layer["n_groups"]), int(layer["state_size"])
    inner = h * hd
    zxbcdt = _product(cast, "bte,ef->btf", x, p["w_in"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    if "conv" not in leave_out:
        k = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = sum(padded[:, i:i + t] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(xbc + p["conv_b"])
    xs, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"] + float(layer.get("dt_origin",
                                                             0.0)))
    y = recurrence(
        xs.reshape(b, t, h, hd), dt, -jnp.exp(p["A_log"]),
        B.reshape(b, t, g, n), C.reshape(b, t, g, n), p["D"],
        int(layer.get("chunk", 128)) if "ssm_carry" in leave_out else None)
    y = y.reshape(b, t, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(b, t, g, inner // g), 1.0,
             float(layer.get("norm_eps", 1e-5))).reshape(b, t, inner)
    return _product(cast, "btf,fe->bte", y * p["gate_norm"], p["w_out"])


def _attention(layer, p, x, cast):
    for flag in ("window", "rope", "qk_norm", "gate"):
        if layer.get(flag):
            raise ValueError(f"this family's attention has no {flag!r}")
    b, t, _ = x.shape
    heads = int(layer["n_heads"])
    kv = int(layer.get("n_kv_heads") or heads)
    d = p["wq"].shape[1] // heads
    q = _product(cast, "bte,ef->btf", x, p["wq"]).reshape(b, t, heads, d)
    k = _product(cast, "bte,ef->btf", x, p["wk"]).reshape(b, t, kv, d)
    v = _product(cast, "bte,ef->btf", x, p["wv"]).reshape(b, t, kv, d)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, t)
    keys = jnp.arange(t)

    @jax.checkpoint
    def some_queries(args):
        qb, first = args
        at = first + jnp.arange(block)
        s = _product(cast, "bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        s = jnp.where((keys[None, :] <= at[:, None])[None, None], s,
                      -jnp.inf)
        return _product(cast, "bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    qs = q.reshape(b, t // block, block, heads, d).transpose(1, 0, 2, 3, 4)
    o = jax.lax.map(some_queries, (qs, jnp.arange(0, t, block)))
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, t, heads * d)
    return _product(cast, "btf,fe->bte", o, p["wo"])


def _relu2_mlp(x, wu, wd, cast):
    h = jnp.square(jax.nn.relu(_product(cast, "bte,ef->btf", x, wu)))
    return _product(cast, "btf,fe->bte", h, wd)


def _routed_experts(layer, p, x, cast, leave_out):
    if layer.get("gated", True) or layer.get("activation") != "relu2":
        raise ValueError("this family's experts are Wd relu(Wu x)^2")
    weights, on_held = route(layer, p, x)
    first = int(layer.get("expert_offset", 0))
    y = jnp.zeros_like(x)
    if "routed_experts" not in leave_out:
        held = p["wu"].shape[0]                  # the experts held here

        def add_expert(y, expert):               # every token through it
            w, wu, wd = expert
            return y + w[..., None] * _relu2_mlp(x, wu, wd, cast), None

        y, _ = jax.lax.scan(add_expert, y, (
            jnp.moveaxis(weights[..., first:first + held], -1, 0),
            p["wu"], p["wd"]))
    if int(layer.get("shared_width", 0)):
        y = y + _relu2_mlp(x, p["shared_wu"], p["shared_wd"], cast)
    return y, on_held.sum()


def make_loss(layers, leave_out=()):
    return _make(json.dumps(layers, sort_keys=True),
                 tuple(sorted(leave_out)))[0]


def make_forward(layers, leave_out=()):
    """``forward(params, rows, cast) -> (logits, {routed layer: routes
    that landed on held experts})``."""
    return _make(json.dumps(layers, sort_keys=True),
                 tuple(sorted(leave_out)))[1]


@functools.lru_cache(maxsize=None)
def _make(layers_json, leave_out):
    layers = json.loads(layers_json)

    def apply(layer, p, xs, cast):
        """(the layer's output, its routes on held experts or None)."""
        kind, x = layer["type"], xs[0]
        if kind == "rms_norm":
            return _rms(x, p["scale"], float(layer.get("eps", 1e-5))), None
        if kind == "add":
            return sum(xs[1:], x), None
        if kind == "mamba2":
            return _mamba2(layer, p, x, cast, leave_out), None
        if kind == "attention":
            return _attention(layer, p, x, cast), None
        if kind == "routed_experts":
            return _routed_experts(layer, p, x, cast, leave_out)
        if kind == "all2all":
            y = _product(cast, "bte,ev->btv", x, p["w"])
            return (y + p["b"] if "b" in p else y), None
        raise ValueError(f"no reference for layer type {kind!r}")

    def forward(params, rows, cast):
        outs, prev, counts = dict(rows), "@input", {}
        for layer in layers:
            name = layer["name"]
            p = params.get(name, {})
            xs = [outs[s] for s in layer.get("inputs", [prev])]
            if layer["type"] == "embedding":
                y = p["table"][xs[0]]
                if layer.get("scale") is not None:
                    y = y * float(layer["scale"])
            else:
                y, n = jax.checkpoint(
                    lambda p, *xs, _l=layer: apply(_l, p, xs, cast))(p, *xs)
                if n is not None:
                    counts[name] = n
            outs[name] = y
            prev = name
        return outs[prev], counts

    def loss_sum(params, rows, cast):
        return cross_entropy_sum(forward(params, rows, cast)[0],
                                 rows["@labels"])

    return loss_sum, forward
