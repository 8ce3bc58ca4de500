"""AlexNet in plain float32 ``jax.numpy``, from Krizhevsky et al. 2012:
the crop and mirror of the stored image, (x - mean) * rdisp, convolution
+ ReLU, local response normalisation b = a / (k + alpha/n * sum a^2)^beta
over n neighbouring channels, overlapping max pooling, dense + ReLU,
dropout (given the multipliers that were drawn), a linear 1000-way layer
and the mean cross-entropy.

Imports nothing of the program.  Reads the layer list of the
configuration's file, so that a test can run it on a cut-down list.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from references.train_steps import cross_entropy_sum


def build_rows(store, labels, idx, offs, flips, crop_hw):
    """What the input pipeline has to deliver for one batch: rows ``idx``
    of the stored images, cropped at ``offs`` to ``crop_hw`` and mirrored
    where ``flips`` says."""
    def one(i, off, flip):
        img = jax.lax.dynamic_slice(
            store[i], (off[0], off[1], 0), (crop_hw, crop_hw, store.shape[3]))
        return jnp.where(flip, img[:, ::-1], img)
    return {"@input": jax.vmap(one)(idx, offs, flips),
            "@labels": labels[idx]}


def _conv(x, w, b, stride, padding, cast):
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    y = jax.lax.conv_general_dilated(
        cast.operand(x), cast.operand(w), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return cast.result(y) + b


def _lrn(x, n, k, alpha, beta):
    half = n // 2
    sq = jnp.pad(jnp.square(x), [(0, 0)] * (x.ndim - 1) + [(half, n - 1 - half)])
    c = x.shape[-1]
    window = sum(sq[..., j:j + c] for j in range(n))
    return x / jnp.power(k + (alpha / n) * window, beta)


def _max_pool(x, window, stride):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def make_loss(layers):
    return _make_loss(json.dumps(layers, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _make_loss(layers_json):
    """``loss_sum(params, rows, cast)`` for this layer list.  ``rows`` holds
    ``@input`` (uint8 images), ``@labels`` and one array of multipliers per
    dropout layer, under the layer's name."""
    layers = json.loads(layers_json)

    def loss_sum(params, rows, cast):
        x = rows["@input"].astype(jnp.float32)
        for layer in layers:
            kind, name = layer["type"], layer["name"]
            p = params.get(name, {})
            if kind == "norm":
                x = (x - layer["mean"]["fill"]) * layer["rdisp"]["fill"]
            elif kind == "conv_relu":
                x = jax.nn.relu(_conv(
                    x, p["w"], p["b"], int(layer.get("stride", 1)),
                    layer.get("padding", "SAME"), cast))
            elif kind == "lrn":
                x = _lrn(x, int(layer.get("n", 5)), float(layer.get("k", 2.0)),
                         float(layer.get("alpha", 1e-4)),
                         float(layer.get("beta", 0.75)))
            elif kind == "max_pooling":
                win = int(layer.get("window", 2))
                x = _max_pool(x, win, int(layer.get("stride") or win))
            elif kind in ("all2all_relu", "softmax", "all2all"):
                x = x.reshape(x.shape[0], -1)
                x = cast.result(cast.operand(x) @ cast.operand(p["w"])) \
                    + p["b"]
                if kind == "all2all_relu":
                    x = jax.nn.relu(x)
            elif kind == "dropout":
                x = x * rows[name]
            else:
                raise ValueError(f"no reference for layer type {kind!r}")
        return cross_entropy_sum(x, rows["@labels"])

    return loss_sum
