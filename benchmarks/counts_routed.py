"""Operations, parameters and bytes from shapes, for configurations whose
block is RMS-normed, gated and routed: the layer types ``counts.py`` does
not know (``rms_norm``, ``gated_mlp``, ``routed_experts``, attention with
a window, a gate and QK-norm), counted by the same rules.

Model FLOPs are the multiply-adds of the matrix products, times 2,
forward plus the two backward products of each.  Causal attention counts
the visible pairs only: half of T x T, less what a sliding window hides.
A routed layer counts what its held experts do for the routes the router
sends them on average (``top_k x experts_held / n_experts`` experts a
token), its shared expert and its router.  Not counted: recomputation,
normalisation, softmax, routing's sort, gather and scatter, the
optimizer, the embedding lookup.

Also here: the grouped products' work and bytes, which the kernel's
roofline share is read against.  Nothing here reads the program.
"""

from __future__ import annotations

from config_io import expand_layers, input_spec, items_per_row


def visible_pairs(t, window=None):
    """Query-key pairs a causal mask leaves visible in T x T, with keys
    more than ``window`` - 1 behind their query hidden."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (t - w) * w


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
        elif kind == "attention":
            t, e = shape
            heads = int(layer["n_heads"])
            kv = int(layer.get("n_kv_heads") or heads)
            d = int(layer.get("head_dim") or e // heads)
            params = e * heads * d * 2 + e * kv * d * 2
            if layer.get("gate"):
                params += e * heads * d
            proj = 2 * t * params
            if layer.get("qk_norm"):
                params += 2 * d
            # scores and weighted values: 2 products over the visible pairs
            flops = proj + 2 * 2 * visible_pairs(t, layer.get("window")) \
                * heads * d
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
                f"counts_routed.py does not know layer type {kind!r}")
        out.append((name, kind, params, flops, shape))
    return out


def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


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


# -- the grouped products of the routed experts ------------------------------

#: a routed layer's grouped products a batch: gate, up and down forward;
#: in training also each one's gradient to the rows and to the matrices
PRODUCTS_FORWARD = 3
PRODUCTS_TRAINED = 9


def routed_layers(cfg):
    """``[{d_model, d_hidden, experts_held}]`` of the routed layers."""
    return [{"d_model": int(cfg["hidden_size"]),
             "d_hidden": int(l["d_hidden"]),
             "experts_held": int(l.get("experts_held") or l["n_experts"])}
            for l in expand_layers(cfg) if l["type"] == "routed_experts"]


def row_forward_flops(layer):
    """One routed row through one expert: gate, up and down."""
    return PRODUCTS_FORWARD * 2 * layer["d_model"] * layer["d_hidden"]


def grouped_products_roof_seconds(layer, rows_train, rows_valid,
                                  active_train, active_valid, batches_train,
                                  peaks, chips=1, bytes_per_element=2):
    """The least seconds the chip could take for one routed layer's
    grouped products over ``rows_train`` routed rows in ``batches_train``
    training batches and ``rows_valid`` routed rows of validation, and
    which bound it is.  ``active_*``: held experts with at least one row,
    summed over the batches.

    Work: a training row three times its forward (forward and the two
    backward products of each), a validation row once.  Bytes: a product
    reads the matrix of every expert that has a row, forward and again
    for the rows' gradient (an expert without rows is not fetched); the
    matrices' own gradient is written for every held expert, zeros
    included; and every product moves its rows in and out (d_model +
    d_hidden elements a row, whichever side is the input)."""
    flops = row_forward_flops(layer) * (3 * rows_train + rows_valid)
    matrix = layer["d_model"] * layer["d_hidden"]
    row = layer["d_model"] + layer["d_hidden"]
    moved = bytes_per_element * (
        matrix * PRODUCTS_FORWARD * (
            2 * active_train + layer["experts_held"] * batches_train
            + active_valid)
        + row * (PRODUCTS_TRAINED * rows_train
                 + PRODUCTS_FORWARD * rows_valid))
    by_work = flops / (peaks["flops_bf16"] * chips)
    by_bytes = moved / (peaks["hbm_bytes_per_s"] * chips)
    return max(by_work, by_bytes), \
        ("compute" if by_work >= by_bytes else "memory")
