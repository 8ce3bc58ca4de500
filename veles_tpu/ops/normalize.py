"""Mean/dispersion normalization op (reference:
ocl/mean_disp_normalizer.cl + veles/mean_disp_normalizer.py:50-138 —
(x - mean) * rdisp elementwise on uint8 input).

One jnp expression: XLA folds the cast+sub+mul into the surrounding ops.
A standalone Pallas kernel lost to it on the chip (512 x 227 x 227 x 3
uint8, TPU v5 lite: XLA 1.49 ms, Pallas 5.21 ms) and is gone.
"""

from __future__ import annotations

import jax.numpy as jnp


def mean_disp_normalize(x, mean, rdisp, dtype=jnp.float32):
    return (x.astype(dtype) - mean.astype(dtype)) * rdisp.astype(dtype)
