"""Operations, parameters and bytes from shapes, for configurations whose
blocks are one mixer each, ``h + mixer(RMS(h))``, the mixers a Mamba-2
state-space layer, causal attention, or routed experts that are not
gated (``Wd act(Wu x)``, two matrices an expert) beside a shared expert
of the same form: the layer types ``mamba2`` and ``add``, and
``routed_experts`` with ``gated`` false, which ``counts.py`` and
``counts_routed.py`` do not know.  Counted by their rules.

Model FLOPs are the multiply-adds of the matrix products, times 2,
forward plus the two backward products of each.  Causal attention counts
the visible pairs only.  A routed layer counts what its held experts do
for the routes the router sends them on average (``top_k x experts_held
/ n_experts`` experts a token), its shared expert and its router.  The
state-space recurrence counts ``4 H P N`` a token forward (the state's
update and its read, a multiply-add each an element of the P x N state of
each of H heads), whatever implements it: a chunked scan does more
arithmetic than that, and what it does beyond is no model work.  Not
counted: recomputation, normalisation, the depthwise convolution (4
taps), softmax, routing's sort, gather and scatter, the optimizer, the
embedding lookup.

Also here: the work and bytes that the grouped products' roofline share
is read against.  Nothing here reads the program.
"""

from __future__ import annotations

from config_io import expand_layers, input_spec, items_per_row


def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def mamba2_widths(layer):
    """(inner, conv_dim, in-projection's outputs) of a ``mamba2`` layer."""
    h, g = int(layer["n_heads"]), int(layer["n_groups"])
    inner = h * int(layer["head_dim"])
    conv_dim = inner + 2 * g * int(layer["state_size"])
    return inner, conv_dim, inner + conv_dim + h


def recurrence_flops_per_token(layer):
    """``4 H P N``: the state's update and its read."""
    return 4 * int(layer["n_heads"]) * int(layer["head_dim"]) \
        * int(layer["state_size"])


def expert_products(layer):
    """Grouped products of one routed row forward: gate, up and down, or
    up and down where the experts are not gated."""
    return 3 if layer.get("gated", True) else 2


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
        elif kind == "mamba2":
            t, e = shape
            inner, conv_dim, proj = mamba2_widths(layer)
            matrices = e * proj + inner * e
            params = matrices + int(layer.get("conv_kernel", 4)) * conv_dim \
                + conv_dim + 3 * int(layer["n_heads"]) + inner
            flops = t * (2 * matrices + recurrence_flops_per_token(layer))
        elif kind == "attention":
            if layer.get("window") or layer.get("gate") \
                    or layer.get("qk_norm"):
                raise ValueError("counts_hybrid.py counts plain causal "
                                 "attention only")
            t, e = shape
            heads = int(layer["n_heads"])
            kv = int(layer.get("n_kv_heads") or heads)
            d = int(layer.get("head_dim") or e // heads)
            params = e * heads * d * 2 + e * kv * d * 2
            # scores and weighted values: 2 products over the visible pairs
            flops = 2 * t * params + 2 * 2 * (t * (t + 1) // 2) * heads * d
        elif kind == "routed_experts":
            t, e = shape
            n, hid = int(layer["n_experts"]), int(layer["d_hidden"])
            held = int(layer.get("experts_held") or n)
            k = expert_products(layer)
            one = k * e * hid
            shared = k * e * int(layer.get("shared_width", 0))
            params = e * n + held * one + shared
            per_token = int(layer["top_k"]) * held / n
            flops = 2 * t * (e * n + per_token * one + shared)
        elif kind == "all2all" and layer.get("per_position"):
            n_in, n_out = shape[-1], int(layer["output_size"])
            params = n_in * n_out + \
                (n_out if layer.get("include_bias", True) else 0)
            flops = 2 * _prod(shape[:-1]) * n_in * n_out
            shape = tuple(shape[:-1]) + (n_out,)
        else:
            raise ValueError(
                f"counts_hybrid.py does not know layer type {kind!r}")
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
    block of ``published.hybrid_override_pattern`` with all the router's
    experts, the whole vocabulary twice (embedding and untied head) and
    the norms.  The check on the cut: it reads the published 31.6 B."""
    published = cfg["published"]
    by_kind = {}
    for layer in expand_layers(cfg):
        by_kind.setdefault(layer["type"], layer)
    e = int(cfg["hidden_size"])
    m = by_kind["mamba2"]
    inner, conv_dim, proj = mamba2_widths(m)
    mamba = e * proj + inner * e + int(m.get("conv_kernel", 4)) * conv_dim \
        + conv_dim + 3 * int(m["n_heads"]) + inner
    a = by_kind["attention"]
    d = int(a["head_dim"])
    attention = 2 * e * d * (int(a["n_heads"]) + int(a["n_kv_heads"]))
    x = by_kind["routed_experts"]
    k = expert_products(x)
    experts = e * int(x["n_experts"]) + k * e * (
        int(x["n_experts"]) * int(x["d_hidden"]) + int(x["shared_width"]))
    pattern = published["hybrid_override_pattern"]
    blocks = {"M": mamba, "*": attention, "E": experts}
    return sum(blocks[c] + e for c in pattern) + e \
        + 2 * int(published["vocab_size"]) * e


# -- the grouped products of experts that are not gated ------------------------

def routed_layers(cfg):
    """``[{d_model, d_hidden, experts_held, products_forward}]`` of the
    routed layers."""
    return [{"d_model": int(cfg["hidden_size"]),
             "d_hidden": int(l["d_hidden"]),
             "experts_held": int(l.get("experts_held") or l["n_experts"]),
             "products_forward": expert_products(l)}
            for l in expand_layers(cfg) if l["type"] == "routed_experts"]


def grouped_products_roof_seconds(layer, rows_train, rows_valid,
                                  active_train, active_valid, batches_train,
                                  peaks, chips=1, bytes_per_element=2):
    """``counts_routed.grouped_products_roof_seconds`` with the layer's
    own products a row (``products_forward``: 2 forward and 6 trained
    where the experts are not gated): the least seconds for one routed
    layer's grouped products, and which bound it is.

    Work: a training row three times its forward, a validation row once.
    Bytes: a product reads the matrix of every expert that has a row,
    forward and again for the rows' gradient; the matrices' own gradient
    is written for every held expert; and every product moves its rows in
    and out (d_model + d_hidden elements a row)."""
    k = layer["products_forward"]
    matrix = layer["d_model"] * layer["d_hidden"]
    flops = k * 2 * matrix * (3 * rows_train + rows_valid)
    row = layer["d_model"] + layer["d_hidden"]
    moved = bytes_per_element * (
        matrix * k * (2 * active_train
                      + layer["experts_held"] * batches_train + active_valid)
        + row * k * (3 * rows_train + rows_valid))
    by_work = flops / (peaks["flops_bf16"] * chips)
    by_bytes = moved / (peaks["hbm_bytes_per_s"] * chips)
    return max(by_work, by_bytes), \
        ("compute" if by_work >= by_bytes else "memory")
