"""Operations, parameters and the routed layers' shapes from shapes, for
configurations whose blocks are ``h + mixer(RMS(h))`` then
``h + mlp(RMS(h))``, the mixers Kimi delta attention
(``kimi_delta_attention``) or latent attention (``attention`` with
``kv_latent``), the MLP gated or routed: layer types and options that
``counts.py``, ``counts_routed.py``, ``counts_hybrid.py`` and
``counts_linear.py`` do not know.  Counted by their rules.

Model FLOPs are the multiply-adds of the matrix products, times 2,
forward plus the two backward products of each.  The delta rule's
recurrence counts ``4 H d d`` a token forward, whatever implements it
(``counts_linear.py`` says why).  The latent attention's core counts the
visible pairs of a causal mask times H times (the key's width + the
value's) times 2: the model's work, not the program's, whose values are
padded to the key's width.  A routed layer counts as
``counts_routed.py`` counts it.  Not counted: recomputation,
normalisation, the depthwise convolutions (4 taps), the decays'
exponentials, softmax, the optimizer, the embedding lookup.

``routed_layers`` gives the routed layers' shapes for
``moe_kernel_roofline``.  Nothing here reads the program.
"""

from __future__ import annotations

from config_io import expand_layers, input_spec, items_per_row
from counts_routed import routed_layers, visible_pairs  # noqa: F401


def kda_params(e, heads, d, taps):
    """(matrices, every other leaf) of a ``kimi_delta_attention`` layer:
    Wq, Wk, Wv, the two low-rank pairs (rank d), Wb and Wo; the three
    convolutions' taps, A_log, dt_bias and the output norm's scale."""
    width = heads * d
    matrices = 3 * e * width + 2 * (e * d + d * width) + e * heads \
        + width * e
    return matrices, 3 * taps * width + heads + width + d


def latent_attention_params(e, heads, d, latent, shared, dv):
    """(matrices, the latent norm's scale) of an ``attention`` layer with
    latent K and V: Wq, W_kv_down, Wk_up, Wv_up, Wo."""
    matrices = e * heads * d + e * (latent + shared) \
        + latent * heads * (d - shared) + latent * heads * dv \
        + heads * dv * e
    return matrices, latent


def walk(cfg, traffic):
    """One entry per layer of one batch row: ``(name, type, params,
    forward_flops, out_shape)``; shapes exclude the batch axis."""
    shape = tuple(input_spec(cfg, traffic)[1:])
    out = []
    for layer in expand_layers(cfg):
        kind, name = layer["type"], layer["name"]
        params = flops = 0
        if kind == "embedding":
            params = int(layer["vocab"]) * int(layer["dim"])
            shape = tuple(shape) + (int(layer["dim"]),)
        elif kind == "rms_norm":
            params = shape[-1]
        elif kind == "add":
            pass
        elif kind == "kimi_delta_attention":
            t, e = shape
            heads, d = int(layer["n_heads"]), int(layer["head_dim"])
            matrices, rest = kda_params(e, heads, d,
                                        int(layer.get("conv_kernel", 4)))
            params = matrices + rest
            flops = t * (2 * matrices + 4 * heads * d * d)
        elif kind == "attention":
            if not layer.get("kv_latent") or layer.get("window") \
                    or layer.get("rope") or layer.get("gate") \
                    or layer.get("qk_norm"):
                raise ValueError("counts_kimi.py counts causal attention "
                                 "with latent K and V and nothing else")
            t, e = shape
            heads, d = int(layer["n_heads"]), int(layer["head_dim"])
            dv = int(layer.get("v_head_dim") or d)
            matrices, scale = latent_attention_params(
                e, heads, d, int(layer["kv_latent"]),
                int(layer.get("k_shared", 0)), dv)
            params = matrices + scale
            # scores (d wide) and weighted values (dv) over the visible
            # pairs
            flops = 2 * t * matrices \
                + 2 * visible_pairs(t) * heads * (d + dv)
        elif kind == "gated_mlp":
            t, e = shape
            params = 3 * e * int(layer["d_hidden"])
            flops = 2 * t * params
        elif kind == "routed_experts":
            t, e = shape
            n, hid = int(layer["n_experts"]), int(layer["d_hidden"])
            held = int(layer.get("experts_held") or n)
            one = 3 * e * hid
            shared = 3 * e * int(layer.get("shared_width", 0))
            params = e * n + held * one + shared
            flops = 2 * t * (e * n + int(layer["top_k"]) * held / n * one
                             + shared)
        elif kind == "all2all" and layer.get("per_position"):
            n_in, n_out = shape[-1], int(layer["output_size"])
            params = n_in * n_out + \
                (n_out if layer.get("include_bias", True) else 0)
            flops = 2 * shape[0] * n_in * n_out
            shape = tuple(shape[:-1]) + (n_out,)
        else:
            raise ValueError(
                f"counts_kimi.py does not know layer type {kind!r}")
        out.append((name, kind, params, flops, shape))
    return out


def model_counts(cfg, traffic):
    """Parameters, and FLOPs per token forward and trained.  An embedding
    upstream is a lookup whose gradient needs every layer's input
    gradient, so each product has two backward products."""
    layers = walk(cfg, traffic)
    per_row = items_per_row(cfg, traffic)
    fwd = sum(l[3] for l in layers)
    return {
        "params": sum(l[2] for l in layers),
        "forward_flops_per_item": fwd / per_row,
        "train_flops_per_item": 3 * fwd / per_row,
        "items_per_row": per_row,
    }


def whole_model_params(cfg):
    """The published model's parameters from this file's widths: every
    layer of ``published.linear_attn_config`` with its mixer, the leading
    dense MLPs and a routed layer of all ``published.num_experts`` after
    them, two norms a layer, the whole vocabulary twice (embedding and
    untied head) and the final norm.  The check on the cut: 49.1 B
    against the published 48 B."""
    published = cfg["published"]
    by_kind = {}
    for layer in expand_layers(cfg):
        by_kind.setdefault(layer["type"], layer)
    e = int(cfg["hidden_size"])
    kda, mla = by_kind["kimi_delta_attention"], by_kind["attention"]
    mixer = sum(kda_params(e, int(kda["n_heads"]), int(kda["head_dim"]),
                           int(kda.get("conv_kernel", 4))))
    latent = sum(latent_attention_params(
        e, int(mla["n_heads"]), int(mla["head_dim"]), int(mla["kv_latent"]),
        int(mla["k_shared"]), int(mla["v_head_dim"])))
    routed = by_kind["routed_experts"]
    one = 3 * e * int(routed["d_hidden"])
    moe = int(published["num_experts"]) * one \
        + 3 * e * int(routed["shared_width"]) + e * int(routed["n_experts"])
    dense = 3 * e * int(by_kind["gated_mlp"]["d_hidden"])
    layers = int(published["num_hidden_layers"])
    lac = published["linear_attn_config"]
    first_dense = int(cfg["first_k_dense_replace"])
    return len(lac["kda_layers"]) * mixer \
        + len(lac["full_attn_layers"]) * latent \
        + first_dense * dense + (layers - first_dense) * moe \
        + 2 * e * layers + e + 2 * int(published["vocab_size"]) * e
