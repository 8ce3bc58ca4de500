"""The Kimi Linear decoder (moonshotai Kimi-Linear-48B-A3B: Kimi delta
attention three layers in four beside latent attention without
positions, routed experts beside a shared one) in plain float32
``jax.numpy``, one chip's share of each routed layer.  The mixers follow
the Kimi Linear report (Moonshot AI 2025, arXiv 2510.26692) and the
model's ``modeling_kimi.py``; the latent attention and the block
Hugging Face ``transformers`` ``models/deepseek_v3/modeling_deepseek_v3.py``
with no rotary embedding (``mla_use_nope``).  A block is
``h <- h + mixer(RMS(h));  h <- h + mlp(RMS(h))``.  Over a layer's H
heads:

    KDA       q = silu(conv_q(x Wq)), k = silu(conv_k(x Wk)), v = silu(conv_v(x Wv))    (H x d each)
              conv: depthwise, causal, K taps, no bias: token t sees t-K+1..t
              a head: q = l2norm(q) / sqrt(d), k = l2norm(k);  l2norm(x) = x rsqrt(sum x^2 + 1e-6)
              g = -exp(A_log[h]) softplus((x Wf_a) Wf_b + dt_bias + dt_origin)    (H x d), by channel
              beta = sigmoid(x Wb)                                                 (H)
              TOKEN BY TOKEN:
                  S_t = Diag(exp(g_t)) S_{t-1}                        (d x d), S_0 = 0
                  S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
                  o_t = S_t^T q_t
              out = (RMS(o) * o_norm * sigmoid((x Wg_a) Wg_b)) Wo                 RMS over a head's d channels
    MLA       q = x Wq  (H x (nope + rope));  [c | k_pe] = x W_kv_down  (latent | rope)
              c = RMS(c; kv_norm);  k_nope = c Wk_up,  v = c Wv_up               (H x nope, H x dv)
              k_h = [k_nope_h | k_pe]: k_pe one row a token for every head;  no positions
              out = softmax(q k^T / sqrt(nope + rope), causal) v Wo
    dense     Wd (silu(Wg x) * (Wu x))
    routed    shared(x) + sum over the top-k held experts of w_e expert_e(x)
              (references/afmoe.py: sigmoid scores, top-k, renormalised, scaled)
    logits = RMS(h; final) W_head

The delta rule is the recurrence itself, not the chunked form the
program computes: an outer scan over blocks of tokens under
``jax.checkpoint`` and an inner scan over a block's tokens.  The
recurrence stays float32 whatever the cast (``references/nemotron_h.py``
says why).  The latent attention computes its values at their own width
(no padding) and goes by blocks of queries.  Imports nothing of the
program.  Reads the layer list of the configuration's file (a layer's
``inputs`` name its sources, by default the layer before; an ``add`` sums
them).  Each layer is rematerialised in the backward pass.

``leave_out`` plants what the check must catch: ``"routed_experts"``
(``references/afmoe.py``'s), ``"delta_carry"`` (the state set to zero
before every ``chunk``-th token, so nothing crosses a chunk's boundary)
and ``"channel_decay"`` (each head's decay the mean of its channels':
one number a head, the scalar-gate rule).  Any other name is another
reference's and changes nothing here.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.afmoe import (_gated, _product, _rms, _routed_experts,
                              build_rows)  # noqa: F401
from references.olmo_hybrid import _conv, l2norm
from references.train_steps import cross_entropy_sum

#: queries taken at a time against all the keys
QUERY_BLOCK = 512
#: tokens of the recurrence rematerialised together
TOKEN_BLOCK = 64


def delta_rule(q, k, v, g, beta, reset_every=None):
    """``o`` (b, t, h, dv) of the recurrence above; q and k (b, t, h, dk),
    v (b, t, h, dv), g (b, t, h, dk), beta (b, t, h).  ``reset_every``:
    the planted fault."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t

    def token(S, a):
        at, qt, kt, vt, gt, bt = a
        if reset_every:
            S = jnp.where(at % reset_every == 0, 0.0, S)
        S = jnp.exp(gt)[..., None] * S
        delta = bt[..., None] * (vt - jnp.einsum("bhde,bhd->bhe", S, kt))
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


def _kda(layer, p, x, cast, leave_out):
    b, t, _ = x.shape
    h, d = int(layer["n_heads"]), int(layer["head_dim"])
    heads = lambda a: a.reshape(b, t, h, d)

    def stream(w, conv_w):
        return heads(jax.nn.silu(_conv(
            _product(cast, "bte,ef->btf", x, w), conv_w)))

    def low_rank(wa, wb):
        return _product(cast, "btr,rf->btf",
                        _product(cast, "bte,er->btr", x, wa), wb)

    q = stream(p["wq"], p["conv_q"])
    k = stream(p["wk"], p["conv_k"])
    v = stream(p["wv"], p["conv_v"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(heads(
        low_rank(p["wf_a"], p["wf_b"]) + p["dt_bias"]
        + float(layer.get("dt_origin", 0.0))))
    if "channel_decay" in leave_out:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_product(cast, "bte,eh->bth", x, p["wb"]))
    o = delta_rule(
        l2norm(q) * d ** -0.5, l2norm(k), v, g, beta,
        int(layer.get("chunk", 64)) if "delta_carry" in leave_out else None)
    y = _rms(o, p["o_norm"], float(layer.get("norm_eps", 1e-5))) \
        * jax.nn.sigmoid(heads(low_rank(p["wg_a"], p["wg_b"])))
    return _product(cast, "btf,fe->bte", y.reshape(b, t, h * d), p["wo"])


def _latent_attention(layer, p, x, cast):
    for flag in ("window", "rope", "gate", "qk_norm"):
        if layer.get(flag):
            raise ValueError(f"this family's attention has no {flag!r}")
    b, t, _ = x.shape
    heads = int(layer["n_heads"])
    d = int(layer["head_dim"])
    latent, rope = int(layer["kv_latent"]), int(layer["k_shared"])
    eps = float(layer.get("norm_eps", 1e-5))
    q = _product(cast, "bte,ef->btf", x, p["wq"]).reshape(b, t, heads, d)
    down = _product(cast, "bte,ef->btf", x, p["w_kv_down"])
    c = _rms(down[..., :latent], p["kv_norm"], eps)
    k_pe = down[..., latent:]
    k_nope = _product(cast, "btc,cf->btf", c, p["wk_up"]).reshape(
        b, t, heads, d - rope)
    v = _product(cast, "btc,cf->btf", c, p["wv_up"]).reshape(
        b, t, heads, -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (b, t, heads, rope))],
        axis=-1)
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
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, t, -1)
    return _product(cast, "btf,fe->bte", o, p["wo"])


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
        if kind == "kimi_delta_attention":
            return _kda(layer, p, x, cast, leave_out), None
        if kind == "attention":
            return _latent_attention(layer, p, x, cast), None
        if kind == "gated_mlp":
            if layer.get("activation", "silu") != "silu":
                raise ValueError("this family's MLP is SwiGLU")
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
