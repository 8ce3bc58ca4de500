"""Operations and parameters from shapes, for configurations whose blocks
are ``h + RMS(mixer(h))`` then ``h + RMS(mlp(h))``, the mixers a gated
delta-rule linear-attention layer or causal attention with a norm over
the whole q and k projections, the MLP gated: the layer types
``gated_delta_net`` and ``attention`` with ``qk_norm: "projection"``,
which ``counts.py``, ``counts_routed.py`` and ``counts_hybrid.py`` do not
know.  Counted by their rules.

Model FLOPs are the multiply-adds of the matrix products, times 2,
forward plus the two backward products of each.  Causal attention counts
the visible pairs only.  The delta rule's recurrence counts ``4 H dk dv``
a token forward (the state's read for the correction and for the output,
a multiply-add each an element of the dk x dv state of each of H heads;
the state's decay and its rank-one update are elementwise), whatever
implements it: a chunked program does more arithmetic than that (the
chunk's Q x Q products and its triangular inverse), and what it does
beyond is no model work.  Heads held are what is counted: a share of a
layer's heads counts its share.  Not counted: recomputation,
normalisation, the depthwise convolutions (4 taps), softmax, the
optimizer, the embedding lookup.

No layer here routes: ``routed_layers`` is empty.  Nothing here reads
the program.
"""

from __future__ import annotations

from config_io import expand_layers, input_spec, items_per_row


def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def delta_net_params(e, heads, dk, dv, taps):
    """(matrices, every other leaf) of a ``gated_delta_net`` layer with
    ``heads`` heads held: Wq, Wk, Wv, Wz, Wb, Wa and Wo; the three
    convolutions' taps, A_log, dt_bias and the gated norm's scale."""
    keys, values = heads * dk, heads * dv
    matrices = e * (2 * keys + 2 * values + 2 * heads) + values * e
    return matrices, taps * (2 * keys + values) + 2 * heads + dv


def recurrence_flops_per_token(layer):
    """``4 H dk dv``: the state's two reads."""
    return 4 * int(layer["n_heads"]) * int(layer["key_dim"]) \
        * int(layer["value_dim"])


def attention_params(e, heads, kv, d):
    """(matrices, the two norms' scales) of an ``attention`` layer whose
    QK norm is over the whole projections."""
    return e * d * 2 * (heads + kv), d * (heads + kv)


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
        elif kind == "gated_delta_net":
            t, e = shape
            matrices, rest = delta_net_params(
                e, int(layer["n_heads"]), int(layer["key_dim"]),
                int(layer["value_dim"]), int(layer.get("conv_kernel", 4)))
            params = matrices + rest
            flops = t * (2 * matrices + recurrence_flops_per_token(layer))
        elif kind == "attention":
            if layer.get("window") or layer.get("gate") or layer.get("rope") \
                    or layer.get("qk_norm") != "projection":
                raise ValueError("counts_linear.py counts causal attention "
                                 "with a norm over the whole projections")
            t, e = shape
            heads = int(layer["n_heads"])
            kv = int(layer.get("n_kv_heads") or heads)
            d = int(layer.get("head_dim") or e // heads)
            matrices, scales = attention_params(e, heads, kv, d)
            params = matrices + scales
            # scores and weighted values: 2 products over the visible pairs
            flops = 2 * t * matrices + 2 * 2 * (t * (t + 1) // 2) * heads * d
        elif kind == "gated_mlp":
            t, e = shape
            params = 3 * e * int(layer["d_hidden"])
            flops = 2 * t * params
        elif kind == "all2all" and layer.get("per_position"):
            n_in, n_out = shape[-1], int(layer["output_size"])
            params = n_in * n_out + \
                (n_out if layer.get("include_bias", True) else 0)
            flops = 2 * _prod(shape[:-1]) * n_in * n_out
            shape = tuple(shape[:-1]) + (n_out,)
        else:
            raise ValueError(
                f"counts_linear.py does not know layer type {kind!r}")
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
    layer of ``published.layer_types`` with all its heads, an MLP and two
    norms a layer, the whole vocabulary twice (embedding and untied head)
    and the final norm.  The check on the cut: it reads the published
    7.43 B."""
    published = cfg["published"]
    by_kind = {}
    for layer in expand_layers(cfg):
        by_kind.setdefault(layer["type"], layer)
    e = int(cfg["hidden_size"])
    g = by_kind["gated_delta_net"]
    linear = sum(delta_net_params(
        e, int(published["linear_num_value_heads"]), int(g["key_dim"]),
        int(g["value_dim"]), int(g.get("conv_kernel", 4))))
    full = sum(attention_params(
        e, int(published["num_attention_heads"]),
        int(published["num_key_value_heads"]),
        int(by_kind["attention"]["head_dim"])))
    mixers = {"linear_attention": linear, "full_attention": full}
    block = 3 * e * int(by_kind["gated_mlp"]["d_hidden"]) + 2 * e
    return sum(mixers[kind] + block for kind in published["layer_types"]) \
        + e + 2 * int(published["vocab_size"]) * e


def routed_layers(cfg):
    """No layer of such a configuration routes."""
    return []
