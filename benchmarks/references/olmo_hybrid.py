"""The Olmo hybrid decoder (allenai Olmo-Hybrid family: gated delta-rule
linear attention, three layers in four, beside full attention) in plain
float32 ``jax.numpy``, one chip's share of each layer's heads.  The
linear layers follow Hugging Face ``transformers``
``models/qwen3_next/modeling_qwen3_next.py`` (``Qwen3NextGatedDeltaNet``,
``torch_recurrent_gated_delta_rule``, ``Qwen3NextRMSNormGated``,
``l2norm``: the config's ``linear_*`` keys are that file's), the block and
the full layers ``models/olmo3/modeling_olmo3.py``.  A block is
``h <- h + RMS(mixer(h));  h <- h + RMS(mlp(h))``: the norm after, none
before.  The mixers, over the H heads held here:

    delta net q = silu(conv_q(x Wq)), k = silu(conv_k(x Wk))  (H x dk), v = silu(conv_v(x Wv))  (H x dv)
              conv: depthwise, causal, K taps, no bias: token t sees t-K+1..t
              z = x Wz;  beta = sigmoid(x Wb) (x 2 with allow_neg_eigval)
              g = -exp(A_log) softplus(x Wa + dt_bias + dt_origin)
              a head: q = l2norm(q) / sqrt(dk), k = l2norm(k);  l2norm(x) = x rsqrt(sum x^2 + 1e-6)
              TOKEN BY TOKEN:
                  S_t = exp(g_t) S_{t-1}                              (dk x dv), S_0 = 0
                  S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
                  o_t = S_t^T q_t
              out = ((RMS(o) * gate_norm) * silu(z)) Wo               RMS over a head's dv channels
    attention q = RMS(x Wq; q_norm), k = RMS(x Wk; k_norm) over ALL the held channels, before
              the heads are split;  v = x Wv;  no positions;  key j visible to query i iff j <= i
              out = softmax(q k^T / sqrt(d)) v Wo
    mlp       Wd (silu(Wg x) * (Wu x))
    logits = RMS(h; final) W_head

The linear layer is the recurrence itself, not the chunked form the
program computes: an outer scan over blocks of tokens under
``jax.checkpoint`` and an inner scan over a block's tokens, which is a
device for the gradient's memory and the same formula.  The recurrence
stays float32 whatever the cast (``references/nemotron_h.py`` says why).
What the heads held elsewhere would add to ``Wo``'s sum is left out, and
the whole-projection norm is over the held channels: in the deployment
the pair of chips would exchange two sums of squares a token, and
``_attention`` takes such sums (``exchanged``) for the one test that adds
the shares up; no run passes them.  Imports nothing of the program.  Reads
the layer list of the configuration's file (a layer's ``inputs`` name its
sources, by default the layer before; an ``add`` sums them).  Each layer
is rematerialised in the backward pass and attention goes by blocks of
queries.

``leave_out`` plants what the check must catch: ``"delta_carry"`` (the
state set to zero before every ``chunk``-th token, so nothing crosses a
chunk's boundary), ``"delta_term"`` (the rule's correction ``S_t^T k_t``
left out, ``S_t = S_t + k_t (beta_t v_t)^T``: plain gated linear
attention) and ``"conv"`` (the convolutions skipped: ``q = silu(x Wq)``
and so on).  Any other name is another reference's and changes nothing
here.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.afmoe import _gated, _product, _rms, build_rows  # noqa: F401
from references.train_steps import cross_entropy_sum

#: queries taken at a time against all the keys
QUERY_BLOCK = 512
#: tokens of the recurrence rematerialised together
TOKEN_BLOCK = 64


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta, reset_every=None, correct=True):
    """``o`` (b, t, h, dv) of the recurrence above; q and k (b, t, h, dk),
    v (b, t, h, dv), g and beta (b, t, h).  ``reset_every`` and
    ``correct=False``: the planted faults.  No ``cast``: see the module's
    docstring."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(S, a):
        at, qt, kt, vt, gt, bt = a
        if reset_every:
            S = jnp.where(at % reset_every == 0, 0.0, S)
        S = jnp.exp(gt)[..., None, None] * S
        seen = jnp.einsum("bhde,bhd->bhe", S, kt) if correct else 0.0
        delta = bt[..., None] * (vt - seen)
        S = S + kt[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhde,bhd->bhe", S, qt)

    @jax.checkpoint
    def some_tokens(S, a):
        return jax.lax.scan(token, S, a)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (t // block, block) + a.shape[:1] + a.shape[2:])
    at = jnp.arange(t).reshape(t // block, block)
    _, o = jax.lax.scan(some_tokens, jnp.zeros((b, h, dk, dv), q.dtype),
                        (at,) + tuple(by_block(a)
                                      for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, b, h, dv), 0, 1)


def _conv(x, w):
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(taps))


def _gated_delta_net(layer, p, x, cast, leave_out):
    b, t, _ = x.shape
    h = int(layer["n_heads"])
    dk, dv = int(layer["key_dim"]), int(layer["value_dim"])

    def stream(w, conv_w):
        y = _product(cast, "bte,ef->btf", x, w)
        return jax.nn.silu(y if "conv" in leave_out else _conv(y, conv_w))

    q = stream(p["wq"], p["conv_q"]).reshape(b, t, h, dk)
    k = stream(p["wk"], p["conv_k"]).reshape(b, t, h, dk)
    v = stream(p["wv"], p["conv_v"]).reshape(b, t, h, dv)
    z = _product(cast, "bte,ef->btf", x, p["wz"]).reshape(b, t, h, dv)
    beta = jax.nn.sigmoid(_product(cast, "bte,eh->bth", x, p["wb"]))
    if layer.get("allow_neg_eigval", False):
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        _product(cast, "bte,eh->bth", x, p["wa"]) + p["dt_bias"]
        + float(layer.get("dt_origin", 0.0)))
    o = delta_rule(
        l2norm(q) * dk ** -0.5, l2norm(k), v, g, beta,
        int(layer.get("chunk", 64)) if "delta_carry" in leave_out else None,
        "delta_term" not in leave_out)
    y = _rms(o, p["gate_norm"], float(layer.get("norm_eps", 1e-6))) \
        * jax.nn.silu(z)
    return _product(cast, "btf,fe->bte", y.reshape(b, t, h * dv), p["wo"])


def qk_sums(p, x, cast):
    """(sum of squares of x Wq, of x Wk) a token over the channels held
    here: what a chip would send its pair for the whole-projection norm."""
    return tuple(jnp.sum(jnp.square(_product(cast, "bte,ef->btf", x, p[w])),
                         axis=-1, keepdims=True) for w in ("wq", "wk"))


def _attention(layer, p, x, cast, exchanged=None):
    """``exchanged``: ((sum of squares of q, of k) a token over every
    share, how many shares); without it the norm is over the held
    channels alone, as every run computes it."""
    if layer.get("qk_norm") != "projection":
        raise ValueError("this family's attention normalises the whole q "
                         "and k projections")
    for flag in ("window", "rope", "gate"):
        if layer.get(flag):
            raise ValueError(f"this family's attention has no {flag!r}")
    b, t, _ = x.shape
    heads = int(layer["n_heads"])
    kv = int(layer.get("n_kv_heads") or heads)
    d = p["wq"].shape[1] // heads
    eps = float(layer.get("norm_eps", 1e-5))
    q = _product(cast, "bte,ef->btf", x, p["wq"])
    k = _product(cast, "bte,ef->btf", x, p["wk"])
    if exchanged is None:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    else:
        (sq, sk), shares = exchanged
        q = q * jax.lax.rsqrt(sq / (shares * q.shape[-1]) + eps) \
            * p["q_norm"]
        k = k * jax.lax.rsqrt(sk / (shares * k.shape[-1]) + eps) \
            * p["k_norm"]
    q = q.reshape(b, t, heads, d)
    k = k.reshape(b, t, kv, d)
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


def make_loss(layers, leave_out=()):
    return _make(json.dumps(layers, sort_keys=True),
                 tuple(sorted(leave_out)))[0]


def make_forward(layers, leave_out=()):
    """``forward(params, rows, cast) -> (logits, {})``: no layer of this
    family routes."""
    return _make(json.dumps(layers, sort_keys=True),
                 tuple(sorted(leave_out)))[1]


@functools.lru_cache(maxsize=None)
def _make(layers_json, leave_out):
    layers = json.loads(layers_json)

    def apply(layer, p, xs, cast):
        kind, x = layer["type"], xs[0]
        if kind == "rms_norm":
            return _rms(x, p["scale"], float(layer.get("eps", 1e-5)))
        if kind == "add":
            return sum(xs[1:], x)
        if kind == "gated_delta_net":
            return _gated_delta_net(layer, p, x, cast, leave_out)
        if kind == "attention":
            return _attention(layer, p, x, cast)
        if kind == "gated_mlp":
            if layer.get("activation", "silu") != "silu":
                raise ValueError("this family's MLP is SwiGLU")
            return _gated(x, p["wg"], p["wu"], p["wd"], cast)
        if kind == "all2all":
            y = _product(cast, "bte,ev->btv", x, p["w"])
            return y + p["b"] if "b" in p else y
        raise ValueError(f"no reference for layer type {kind!r}")

    def forward(params, rows, cast):
        outs, prev = dict(rows), "@input"
        for layer in layers:
            name = layer["name"]
            p = params.get(name, {})
            xs = [outs[s] for s in layer.get("inputs", [prev])]
            if layer["type"] == "embedding":
                y = p["table"][xs[0]]
                if layer.get("scale") is not None:
                    y = y * float(layer["scale"])
            else:
                y = jax.checkpoint(
                    lambda p, *xs, _l=layer: apply(_l, p, xs, cast))(p, *xs)
            outs[name] = y
            prev = name
        return outs[prev], {}

    def loss_sum(params, rows, cast):
        return cross_entropy_sum(forward(params, rows, cast)[0],
                                 rows["@labels"])

    return loss_sum, forward
