"""The plain training reference: a few optimizer steps in float32.

Imports nothing of the program.  A model's reference (``alexnet.py``,
``opt_postln.py``) gives ``loss_sum(params, rows, cast)``: the summed
cross-entropy of a block of rows.  Here that is differentiated block by
block (so that a full batch fits beside nothing else), the gradients are
added up and divided by the batch, and the optimizer of the published
recipe takes its step.  ``cast`` is how every matrix product and
convolution is rounded (a ``Cast``): not at all for the reference itself,
to a lower precision for the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# -- rounding: how a precision is put on ---------------------------------------

class Cast:
    """How the operands and the result of every matrix product and
    convolution are rounded.  ``operand`` rounds what goes into a product
    on the way forward and the gradient that comes back out of it;
    ``result`` rounds what comes out of it forward and the gradient that
    goes back into it.  So the forward products and both backward products
    take rounded operands and give rounded results, as they do in a
    program that computes in that precision."""

    def __init__(self, name, rounding=None):
        self.name = name
        self.operand = self.result = \
            _both_ways(rounding) if rounding else (lambda x: x)

    def __hash__(self):
        return hash(self.name)

    def __eq__(self, other):
        return isinstance(other, Cast) and other.name == self.name


def _both_ways(rounding):
    @jax.custom_vjp
    def rounded(x):
        return rounding(x)

    rounded.defvjp(lambda x: (rounding(x), None),
                   lambda _, g: (rounding(g),))
    return rounded


def _via(dtype):
    return lambda x: x.astype(dtype).astype(jnp.float32)


CASTS = {
    "float32": Cast("float32"),
    "bfloat16": Cast("bfloat16", _via(jnp.bfloat16)),
    # the control: what the program's own switch (compute_dtype) would do
    # with the next type down, a plain cast as it casts to bfloat16
    "float8": Cast("float8", _via(jnp.float8_e4m3fn)),
}
cast_float32 = CASTS["float32"]


# -- the steps ---------------------------------------------------------------

def tree_zeros(tree):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), tree)


def batch_gradient(loss_sum, params, rows, n_rows, block_rows, cast):
    """(mean loss, gradient of the mean loss) over ``rows``: a dict of
    arrays with the batch on axis 0, taken ``block_rows`` at a time."""
    grad_block = _grad_block(loss_sum, cast)
    total = jnp.zeros((), jnp.float32)
    grads = tree_zeros(params)
    items = 0
    for lo in range(0, n_rows, block_rows):
        block = {k: v[lo:lo + block_rows] for k, v in rows.items()}
        (ce, n), g = grad_block(params, block)
        total = total + ce
        items += int(n)
        grads = jax.tree.map(jnp.add, grads, g)
    scale = 1.0 / items
    return total * scale, jax.tree.map(lambda g: g * scale, grads)


@functools.lru_cache(maxsize=None)
def _grad_block(loss_sum, cast):
    return jax.jit(jax.value_and_grad(
        lambda p, rows: loss_sum(p, rows, cast), has_aux=True))


@jax.jit
def _momentum_update(params, vel, grads, lr, momentum, l2):
    g = jax.tree.map(lambda g, p: g + l2 * p, grads, params)
    vel = jax.tree.map(lambda v, g: momentum * v + g, vel, g)
    return jax.tree.map(lambda p, v: p - lr * v, params, vel), vel


@jax.jit
def _adam_update(params, m, v, grads, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def step(p, m, v):
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps)

    return jax.tree.map(step, params, m, v), m, v


def leaf_norms(tree):
    """{path: float64 L2 norm} of a nested dict of arrays."""
    flat = jax.tree_util.tree_leaves_with_path(tree)
    norms = jax.device_get([jnp.sqrt(jnp.sum(jnp.square(
        leaf.astype(jnp.float32)))) for _, leaf in flat])
    return {leaf_name(path): float(n) for (path, _), n in zip(flat, norms)}


def leaf_name(path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def follow(loss_sum, params0, batches, *, optimizer, optimizer_args,
           block_rows, cast=cast_float32, precision="highest"):
    """Drive ``len(batches)`` steps from ``params0``.  Returns each step's
    loss (before its update), the first gradient's norm by leaf and the
    norm by leaf of the parameters' change over all the steps."""
    args = dict(optimizer_args)
    lr = float(args.get("lr", 0.01 if optimizer == "momentum" else 1e-3))
    params = params0
    slots = (tree_zeros(params0),) if optimizer == "momentum" else \
        (tree_zeros(params0), tree_zeros(params0))
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for t, rows in enumerate(batches, start=1):
            n_rows = int(next(iter(rows.values())).shape[0])
            loss, grads = batch_gradient(loss_sum, params, rows, n_rows,
                                         block_rows, cast)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = leaf_norms(grads)
            if optimizer == "momentum":
                params, vel = _momentum_update(
                    params, slots[0], grads, lr,
                    float(args.get("momentum", 0.9)),
                    float(args.get("l2", 0.0)))
                slots = (vel,)
            elif optimizer == "adam":
                params, m, v = _adam_update(
                    params, slots[0], slots[1], grads, float(t), lr,
                    float(args.get("b1", 0.9)), float(args.get("b2", 0.999)),
                    float(args.get("eps", 1e-8)))
                slots = (m, v)
            else:
                raise ValueError(f"no reference for optimizer {optimizer!r}")
            del grads
    change = leaf_norms(jax.tree.map(jnp.subtract, params, params0))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}


# -- the shared pieces of a forward pass -------------------------------------

def cross_entropy_sum(logits, labels):
    """Summed cross-entropy of integer labels over every leading axis, and
    how many were summed."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return ce.sum(), jnp.asarray(np.prod(labels.shape), jnp.int32)
