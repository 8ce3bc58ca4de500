"""Loss ops (the reference's "evaluators": softmax cross-entropy and MSE,
docs manualrst_veles_algorithms.rst:157 item 7; the Znicz EvaluatorSoftmax /
EvaluatorMSE units plugged between forwards and gradient units)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


#: Logits of at least this many bytes as float32 (rows x classes x 4) take
#: the swept path on a TPU (ops/pallas_kernels.py ``softmax_xent_rows``:
#: one Pallas sweep forward, the gradient computed where it is consumed).
#: Below it the plain jnp formulation runs, bit for bit as before.  From
#: the chip (PERF.md section 6, PR 30), a head with its loss and gradients,
#: plain against swept by the logits' size: 2 MB (AlexNet's 512 x 1000)
#: 0.158 / 0.132 ms, 32 MiB 0.390 / 0.346, 64 MiB 0.825 / 0.691, 128 MiB
#: 2.26 / 1.53, 1.65 GB (an LM's 8192 x 50,272) 30.66 / 18.07.  Swept is
#: never slower; under 32 MiB it saves tens of microseconds, less than a
#: thousandth of any step that has such a head, so small classifiers keep
#: the program they had.
SWEPT_MIN_BYTES = 32 << 20


def _mesh_row_axes(mesh, n_rows):
    """The axes a multi-device ``mesh`` shards ``n_rows`` leading rows
    over, if they cover every device; None where they do not tile the
    rows or leave an axis that could shard the classes."""
    from ..parallel.mesh import batch_axes
    axes = batch_axes(mesh, n_rows)
    names = (axes,) if isinstance(axes, str) else axes or ()
    covered = math.prod(mesh.shape[a] for a in names)
    return axes if covered == mesh.size else None


def softmax_loss_path(shape, mesh=None) -> str:
    """``"swept"`` or ``"plain"`` for logits of ``shape``, from what the
    call can see: the logits' size, the backend, and the mesh the call is
    traced under (None outside GSPMD).  Under a multi-device mesh the
    kernels run per shard of rows, so the batch axes have to cover the
    whole mesh: where another axis could shard the classes, plain stays."""
    from . import use_pallas_default
    if math.prod(shape) * 4 < SWEPT_MIN_BYTES or not use_pallas_default():
        return "plain"
    if mesh is not None and mesh.size > 1 \
            and _mesh_row_axes(mesh, shape[0]) is None:
        return "plain"
    return "swept"


def _rows_plain(logits, labels):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return ce, jnp.argmax(logits, axis=-1)


def _rows_swept(logits, labels, mesh):
    from .pallas_kernels import softmax_xent_rows

    def rows(logits, labels):  # shard-map-root: data,fsdp
        ce, pred = softmax_xent_rows(
            logits.reshape(-1, logits.shape[-1]), labels.reshape(-1))
        return ce.reshape(labels.shape), pred.reshape(labels.shape)

    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import shard_batch
        by_row = P(_mesh_row_axes(mesh, logits.shape[0]),
                   *(None,) * (labels.ndim - 1))
        rows = shard_batch(rows, mesh, (P(*by_row, None), by_row),
                           (by_row, by_row))
    return rows(logits, labels)


def softmax_cross_entropy(logits, labels, *, mask=None, mesh=None):
    """Mean CE over the batch; labels are integer class ids.

    Returns (loss, n_err) — n_err is the reference's per-minibatch error
    count that Decision accumulated into epoch error rates.

    Per row the loss needs the logsumexp, the label's logit and the first
    index of the maximum.  Small logits get them from ``log_softmax``,
    ``take_along_axis`` and ``argmax`` with autodiff's backward; large
    ones on a TPU from one sweep, with a backward of their own
    (``softmax_loss_path``).  Arithmetic is float32 either way.  ``mesh``
    is the GSPMD mesh the call is traced under, if any."""
    if softmax_loss_path(logits.shape, mesh) == "swept":
        ce, pred = _rows_swept(logits, labels, mesh)
    else:
        ce, pred = _rows_plain(logits, labels)
    err = (pred != labels).astype(jnp.float32)
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        return (ce * mask).sum() / denom, (err * mask).sum()
    return ce.mean(), err.sum()


def mse_loss(output, target, *, mask=None, root_flag=False):
    """Mean squared error; returns (loss, sum of per-sample sq-norm errors)
    so RMSE can be aggregated per epoch (reference AE RMSE metric,
    manualrst_veles_algorithms.rst:71)."""
    output = output.astype(jnp.float32)
    target = target.astype(jnp.float32)
    diff = output.reshape(output.shape[0], -1) - target.reshape(
        target.shape[0], -1)
    per_sample = jnp.mean(jnp.square(diff), axis=-1)
    if mask is not None:
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (per_sample * mask).sum() / denom
        agg = (per_sample * mask).sum()
    else:
        loss = per_sample.mean()
        agg = per_sample.sum()
    if root_flag:
        loss = jnp.sqrt(loss)
    return loss, agg
