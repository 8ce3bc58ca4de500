"""A post-LayerNorm decoder (the OPT-350m block, Zhang et al. 2022, with
rotary positions, Su et al. 2021) in plain float32 ``jax.numpy``:

    h = LN(x + Attn(x));  y = LN(h + W2 relu(W1 h + b1) + b2)

Attention is full causal multi-head softmax(q k^T / sqrt(d)) v with the
rotary embedding on q and k: pairs (x[2i], x[2i+1]) of each head turned by
the angle position / 10000^(2i/d).  Logits are a linear layer on every
position; the loss is the mean cross-entropy of the next token.

Imports nothing of the program.  Reads the layer list of the
configuration's file, so that a test can run it on a cut-down list.
Each block is rematerialised in the backward pass so that one row of
2048 tokens at float32 stays small.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.train_steps import cross_entropy_sum


def build_rows(tokens, idx):
    """Rows ``idx`` of the token store: inputs are all but the last id of
    a row, labels all but the first."""
    rows = tokens[idx]
    return {"@input": rows[:, :-1], "@labels": rows[:, 1:]}


def _rope(x, base=10000.0):
    b, t, h, d = x.shape
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(b, t, h, d)


def _product(cast, spec, a, b):
    return cast.result(jnp.einsum(spec, cast.operand(a), cast.operand(b)))


def _attention(x, p, heads, rope, cast):
    b, t, e = x.shape
    d = p["wq"].shape[1] // heads
    q, k, v = (_product(cast, "bte,ef->btf", x, p[w]).reshape(b, t, heads, d)
               for w in ("wq", "wk", "wv"))
    if rope:
        q, k = _rope(q), _rope(k)
    s = _product(cast, "bqhd,bkhd->bhqk", q, k) * d ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o = _product(cast, "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _product(cast, "btf,fe->bte", o.reshape(b, t, heads * d), p["wo"])


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["shift"]


def make_loss(layers):
    return _make_loss(json.dumps(layers, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _make_loss(layers_json):
    """``loss_sum(params, rows, cast)`` for this layer list; ``rows`` holds
    ``@input`` and ``@labels``, both (rows, T) token ids."""
    layers = json.loads(layers_json)

    def apply(layer, p, x, cast):
        kind = layer["type"]
        if kind == "attention":
            y = _attention(x, p, int(layer["n_heads"]),
                           bool(layer.get("rope")), cast)
            return x + y if layer.get("residual") else y
        if kind == "layer_norm":
            return _layer_norm(x, p, float(layer.get("eps", 1e-5)))
        if kind == "ffn":
            h = jax.nn.relu(_product(cast, "bte,ef->btf", x, p["w1"])
                            + p["b1"])
            y = _product(cast, "btf,fe->bte", h, p["w2"]) + p["b2"]
            return x + y if layer.get("residual", True) else y
        if kind == "all2all":
            return _product(cast, "bte,ev->btv", x, p["w"]) + p["b"]
        raise ValueError(f"no reference for layer type {kind!r}")

    def loss_sum(params, rows, cast):
        x = None
        for layer in layers:
            p = params.get(layer["name"], {})
            if layer["type"] == "embedding":
                x = p["table"][rows["@input"]]
                continue
            x = jax.checkpoint(
                lambda p, x, _l=layer: apply(_l, p, x, cast))(p, x)
        return cross_entropy_sum(x, rows["@labels"])

    return loss_sum
