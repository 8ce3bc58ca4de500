"""The AFMoE decoder block (arcee-ai Trinity family; Hugging Face
``transformers`` ``models/afmoe/modeling_afmoe.py``) in plain float32
``jax.numpy``, one chip's share of each layer:

    h0 = table[ids] * scale
    a  = RMS(h; in);  q = RMSq(a Wq), k = RMSk(a Wk) per head,  v = a Wv
         sliding layer: rotary on q, k; key j visible to query i iff 0 <= i - j < window
         full layer:    no positions;   key j visible iff j <= i
    o  = softmax(q k^T / sqrt(d)) v;   attn = (o * sigmoid(a Wg)) Wo
    h  = h + RMS(attn; post_attn)
    m  = RMS(h; pre_mlp)
    f  = Wd(silu(Wg m) * (Wu m))                                  dense layer
    f  = shared(m) + sum_{e in top_k} w_e expert_e(m)             routed layer
         s = sigmoid(m Wr);  top_k of (s + b), b = 0;  w = s[top_k] / (sum + 1e-20) * route_scale
         a route to an expert that is not held adds nothing
    h  = h + RMS(f; post_mlp)
    logits = RMS(h; final) W_head

Rotary pairs are (x[2i], x[2i+1]) of each head, angle position /
theta^(2i/d).  The router's product and scores stay float32 whatever the
cast.  Imports nothing of the program.  Reads the layer list of the
configuration's file (a layer's ``inputs`` name its sources, by default
the layer before; a ``rms_norm`` with two adds its second), so a test can
run it on a cut-down list.  Each layer is rematerialised in the backward
pass and attention goes by blocks of queries, so that one row of 4096
tokens stays small.

``leave_out`` plants what the check must catch: ``"routed_experts"``
(the routed experts' sum left out, the shared expert kept) and
``"window"`` (sliding layers attend to every earlier key).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.train_steps import cross_entropy_sum

#: queries taken at a time against all the keys
QUERY_BLOCK = 512


def build_rows(tokens, idx):
    """Rows ``idx`` of the token store: inputs are all but the last id of
    a row, labels all but the first."""
    rows = tokens[idx]
    return {"@input": rows[:, :-1], "@labels": rows[:, 1:]}


def _product(cast, spec, a, b):
    return cast.result(jnp.einsum(spec, cast.operand(a), cast.operand(b)))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, base=10000.0):
    b, t, h, d = x.shape
    half = d // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(b, t, h, d)


def _attention(layer, p, x, cast, leave_out):
    b, t, _ = x.shape
    heads = int(layer["n_heads"])
    kv = int(layer.get("n_kv_heads") or heads)
    d = p["wq"].shape[1] // heads
    eps = float(layer.get("norm_eps", 1e-5))
    window = None if "window" in leave_out else layer.get("window")
    q = _product(cast, "bte,ef->btf", x, p["wq"]).reshape(b, t, heads, d)
    k = _product(cast, "bte,ef->btf", x, p["wk"]).reshape(b, t, kv, d)
    v = _product(cast, "bte,ef->btf", x, p["wv"]).reshape(b, t, kv, d)
    if layer.get("qk_norm"):
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if layer.get("rope"):
        q, k = _rope(q), _rope(k)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, t)
    keys = jnp.arange(t)

    @jax.checkpoint
    def some_queries(args):
        qb, first = args
        at = first + jnp.arange(block)
        s = _product(cast, "bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        seen = keys[None, :] <= at[:, None]
        if window is not None:
            seen &= at[:, None] - keys[None, :] < int(window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return _product(cast, "bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    starts = jnp.arange(0, t, block)
    qs = q.reshape(b, t // block, block, heads, d).transpose(1, 0, 2, 3, 4)
    o = jax.lax.map(some_queries, (qs, starts))
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, t, heads * d)
    if layer.get("gate"):
        o = o * jax.nn.sigmoid(_product(cast, "bte,ef->btf", x, p["wg"]))
    return _product(cast, "btf,fe->bte", o, p["wo"])


def _gated(x, wg, wu, wd, cast):
    h = jax.nn.silu(_product(cast, "bte,ef->btf", x, wg)) \
        * _product(cast, "bte,ef->btf", x, wu)
    return _product(cast, "btf,fe->bte", h, wd)


def route(layer, p, x):
    """(weights (.., n_experts) that are zero off the chosen top_k, and
    whether each chosen expert is held here (.., top_k))."""
    n, k = int(layer["n_experts"]), int(layer["top_k"])
    held = int(layer.get("experts_held") or n)
    first = int(layer.get("expert_offset", 0))
    logits = jnp.einsum("bte,en->btn", x, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, top = jax.lax.top_k(jax.lax.stop_gradient(s), k)   # b = 0
    w = jnp.take_along_axis(s, top, axis=-1)
    if layer.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(layer.get("route_scale", 1.0))
    chosen = jax.nn.one_hot(top, n, dtype=w.dtype)        # (b, t, k, n)
    return jnp.einsum("btk,btkn->btn", w, chosen), \
        (top >= first) & (top < first + held)


def _routed_experts(layer, p, x, cast, leave_out):
    weights, on_held = route(layer, p, x)
    first = int(layer.get("expert_offset", 0))
    y = jnp.zeros_like(x)
    if "routed_experts" not in leave_out:
        held = p["wg"].shape[0]                  # the experts held here

        def add_expert(y, expert):               # every token through it
            w, wg, wu, wd = expert
            return y + w[..., None] * _gated(x, wg, wu, wd, cast), None

        y, _ = jax.lax.scan(add_expert, y, (
            jnp.moveaxis(weights[..., first:first + held], -1, 0),
            p["wg"], p["wu"], p["wd"]))
    if int(layer.get("shared_width", 0)):
        y = y + _gated(x, p["shared_wg"], p["shared_wu"], p["shared_wd"],
                       cast)
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
            y = _rms(x, p["scale"], float(layer.get("eps", 1e-5)))
            return (xs[1] + y if len(xs) > 1 else y), None
        if kind == "attention":
            return _attention(layer, p, x, cast, leave_out), None
        if kind == "gated_mlp":
            return _gated(x, p["wg"], p["wu"], p["wd"], cast), None
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
