"""``train_steps.follow`` for a model whose float32 state nearly fills the
device: Adam's steps taken leaf by leaf.

``train_steps.follow`` keeps five parameter-sized float32 trees (the
parameters, m, v, the summed gradient and a block's gradient) and updates
whole trees at once, old and new alive together.  Here a batch is one
block (the cells this serves have one row a batch, or few), so the
block's gradient is the gradient; m and v do not exist before the first
update; each leaf is updated on its own and the old one dropped; and the
first parameters are not kept: ``remake_leaf(path)`` makes one again for
the norm of its change.  Four trees at most, three at the first step.

Imports nothing of the program.  Adam only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references.train_steps import (_adam_update, cast_float32, leaf_name,
                                    leaf_norms)


@functools.lru_cache(maxsize=None)
def _grad(loss_sum, cast):
    return jax.jit(jax.value_and_grad(
        lambda p, rows: loss_sum(p, rows, cast), has_aux=True))


def follow(loss_sum, params, batches, remake_leaf, *, optimizer_args,
           cast=cast_float32, precision="highest"):
    """Drive ``len(batches)`` Adam steps from ``params`` (which the caller
    gives up: hold no other reference to it).  Returns each step's loss
    (before its update), the first gradient's norm by leaf and the norm by
    leaf of the parameters' change over all the steps, as
    ``train_steps.follow`` does."""
    args = dict(optimizer_args)
    lr = float(args.get("lr", 1e-3))
    b1, b2 = float(args.get("b1", 0.9)), float(args.get("b2", 0.999))
    eps = float(args.get("eps", 1e-8))
    grad = _grad(loss_sum, cast)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [path for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    del flat, params
    m = v = None
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for t, rows in enumerate(batches, start=1):
            (ce, n), grads = grad(treedef.unflatten(leaves), rows)
            scale = 1.0 / int(n)
            losses.append(float(ce) * scale)
            if first_grad is None:
                first_grad = {leaf: norm * scale
                              for leaf, norm in leaf_norms(grads).items()}
                m, v = [None] * len(leaves), [None] * len(leaves)
            grads = jax.tree_util.tree_leaves(grads)
            for i in range(len(leaves)):
                g, grads[i] = grads[i] * scale, None
                zeros = jnp.zeros_like(g)
                leaves[i], m[i], v[i] = _adam_update(
                    leaves[i], zeros if m[i] is None else m[i],
                    zeros if v[i] is None else v[i], g, float(t), lr, b1,
                    b2, eps)
    change = {}
    for path, leaf in zip(paths, leaves):
        change[leaf_name(path)] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf - remake_leaf(path)))))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}
