"""Operations, parameters and bytes from shapes: the benchmark's own count.

Model FLOPs are the multiply-adds of the convolutions and matrix products
that the forward pass and the two backward products of each need, times 2.
Not counted: recomputation, the LRN window sum (however the program
formulates it), pooling, normalisation, softmax, the optimizer, the
embedding lookup.  Causal attention counts the lower triangle only, half
of T x T.  The first layer's input gets no gradient, so its backward is
one product, not two.

Nothing here reads the program or XLA's ``cost_analysis()``.
"""

from __future__ import annotations

from config_io import expand_layers, input_spec, items_per_row


def _conv_out(size, k, stride, padding):
    if padding == "VALID":
        return (size - k) // stride + 1
    if padding == "SAME":
        return -(-size // stride)
    return (size + 2 * int(padding) - k) // stride + 1


def walk(cfg, traffic):
    """One entry per layer of one batch row: ``(name, type, params,
    forward_flops, needs_input_grad, out_shape)``; shapes exclude the batch
    axis."""
    shape = tuple(input_spec(cfg, traffic)[1:])
    out = []
    first_weighted = True
    for layer in expand_layers(cfg):
        kind, name = layer["type"], layer["name"]
        params = flops = 0
        weighted = False
        if kind.startswith("conv"):
            h, w, cin = shape
            kx = int(layer["kx"])
            ky = int(layer.get("ky") or kx)
            stride = int(layer.get("stride", 1))
            pad = layer.get("padding", "SAME")
            ho, wo = _conv_out(h, ky, stride, pad), \
                _conv_out(w, kx, stride, pad)
            cout = int(layer["n_kernels"])
            params = kx * ky * cin * cout + cout
            flops = 2 * kx * ky * cin * cout * ho * wo
            shape, weighted = (ho, wo, cout), True
        elif kind in ("max_pooling", "avg_pooling"):
            h, w, c = shape
            win = int(layer.get("window", 2))
            stride = int(layer.get("stride") or win)
            shape = ((h - win) // stride + 1, (w - win) // stride + 1, c)
        elif kind.startswith("all2all") or kind == "softmax":
            n_out = int(layer["output_size"])
            if layer.get("per_position"):
                rows, n_in = _prod(shape[:-1]), shape[-1]
                shape = tuple(shape[:-1]) + (n_out,)
            else:
                rows, n_in = 1, _prod(shape)
                shape = (n_out,)
            params = n_in * n_out + n_out
            flops = 2 * rows * n_in * n_out
            weighted = True
        elif kind == "embedding":
            params = int(layer["vocab"]) * int(layer["dim"])
            shape = tuple(shape) + (int(layer["dim"]),)
        elif kind == "attention":
            t, e = shape
            heads = int(layer["n_heads"])
            kv = int(layer.get("n_kv_heads") or heads)
            d = int(layer.get("head_dim") or e // heads)
            params = e * heads * d * 2 + e * kv * d * 2
            proj = 2 * t * params
            # scores and weighted values: 2 products of T x T x (H*D),
            # the causal half of them
            core = 2 * 2 * t * t * heads * d
            if layer.get("causal", True):
                core //= 2
            flops = proj + core
            weighted = True
        elif kind == "ffn":
            t, e = shape
            hid = int(layer["d_hidden"])
            params = 2 * e * hid + hid + e
            flops = 2 * t * 2 * e * hid
            weighted = True
        elif kind == "layer_norm":
            params = 2 * shape[-1]
        elif kind in ("lrn", "dropout", "norm", "flatten"):
            pass
        else:
            raise ValueError(f"counts.py does not know layer type {kind!r}")
        out.append((name, kind, params, flops,
                    weighted and not first_weighted, shape))
        if weighted:
            first_weighted = False
    return out


def _prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def model_counts(cfg, traffic):
    """Parameters, and FLOPs per item (image or token) forward and
    trained."""
    layers = walk(cfg, traffic)
    per_row = items_per_row(cfg, traffic)
    fwd = sum(l[3] for l in layers)
    # backward: the product for the weights always, the product for the
    # input where something upstream needs a gradient.  An embedding
    # upstream is a lookup: its gradient needs the input gradient.
    has_table = any(l[1] == "embedding" for l in layers)
    bwd = sum(l[3] * (2 if (l[4] or has_table) else 1) for l in layers)
    return {
        "params": sum(l[2] for l in layers),
        "forward_flops_per_item": fwd / per_row,
        "train_flops_per_item": (fwd + bwd) / per_row,
        "items_per_row": per_row,
    }
