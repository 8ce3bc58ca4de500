"""Device mesh + sharding rules.

This module replaces the ENTIRE distributed stack of the reference — the
Twisted TCP control plane, ZeroMQ data plane, master-slave job protocol and
serialized Python gradient merging (reference: veles/server.py:659,
veles/client.py:405, veles/txzmq/connection.py:97, SURVEY.md §2.5) — with
the TPU-native SPMD model: a ``jax.sharding.Mesh`` over ICI/DCN, sharding
annotations on the workflow state pytree, and XLA-inserted collectives
(psum for gradients riding ICI instead of pickles riding TCP).

Axes (any may be size 1):
  * ``data``  — batch-dimension data parallelism (the reference's only
                scaling axis: minibatch jobs to slaves),
  * ``fsdp``  — parameter sharding across data-parallel workers
                (ZeRO-style; absent in the reference, required at TPU scale),
  * ``model`` — tensor parallelism for wide layers,
  * ``seq``   — sequence/context parallelism for ring attention.

Rules are functions ``(path, spec) -> PartitionSpec`` applied over the
workflow state; GSPMD propagates everything else.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass
class MeshSpec:
    """Declarative mesh description; -1 = absorb remaining devices.

    Axes: data (DP), fsdp (ZeRO), model (TP), seq (ring attention),
    pipe (pipeline stages), expert (MoE banks)."""
    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def axis_sizes(self, n_devices: int) -> Dict[str, int]:
        sizes = {"data": self.data, "fsdp": self.fsdp,
                 "model": self.model, "seq": self.seq,
                 "pipe": self.pipe, "expert": self.expert}
        fixed = math.prod(v for v in sizes.values() if v > 0)
        wild = [k for k, v in sizes.items() if v == -1]
        if wild:
            rem = n_devices // fixed
            for k in wild[:-1]:
                sizes[k] = 1
            sizes[wild[-1]] = rem
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} does not tile {n_devices} devices")
        return sizes


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh; defaults to pure data parallelism over all devices.

    Axis order is (data, fsdp, model, seq): the innermost axes get
    ICI-neighbor device ranges, which is where tensor/sequence parallel
    traffic belongs (scaling-book recipe)."""
    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        # root.common.mesh (default ``{"data": -1}``) is the config-tree
        # form of MeshSpec: axis name -> size, -1 absorbing the rest
        # (docs/configuration.md)
        from ..config import root
        axes = {k: int(v) for k, v in root.common.mesh.items()}
        spec = MeshSpec(**axes) if axes else MeshSpec()
    sizes = spec.axis_sizes(len(devices))
    names = ("data", "fsdp", "model", "seq", "pipe", "expert")
    arr = np.asarray(devices).reshape(*(sizes[n] for n in names))
    return Mesh(arr, names)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """``jax.shard_map``; the one name every veles_tpu call site (and
    the analysis registry's shard_map scopes) goes through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def batch_axes(mesh: Mesh, n: int):
    """The mesh axes a leading (batch) dimension of extent ``n`` is
    sharded over: data×fsdp (fsdp workers are data-parallel too), or
    None when neither is > 1 or their product does not tile ``n``.
    One definition for the step's batch shardings and for the kernels
    shard_map'd under them (:func:`shard_batch`)."""
    dp = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    if not dp or n % math.prod(mesh.shape[a] for a in dp):
        return None
    return dp if len(dp) > 1 else dp[0]


def shard_batch(f, mesh: Mesh, specs: Sequence[P], out_spec: P):
    """``f`` run per shard of a multi-device ``mesh``, each operand split
    as its spec says and replicated over every other axis.

    Why it exists: a Mosaic (Pallas TPU) kernel inside a GSPMD-partitioned
    jit does not compile — XLA cannot partition the custom call and says
    so ("Mosaic kernels cannot be automatically partitioned").  The kernel
    call sites (attention, dropout) therefore hand the partitioner a
    manual region in which each device runs the kernel on its own rows.
    On a one-device mesh there is nothing to partition and ``f`` is
    returned as is."""
    if mesh.size == 1:
        return f
    return shard_map(f, mesh=mesh, in_specs=tuple(specs),
                     out_specs=out_spec, check_vma=False)


# -- sharding rules ----------------------------------------------------------

Rule = Callable[[Tuple[str, ...], jax.ShapeDtypeStruct], P]


def data_parallel_rules(path, spec) -> P:
    """Replicate everything (grads psum'd by GSPMD): classic DP, the direct
    analog of the reference's master-applied weight deltas."""
    return P()


def fsdp_rules(min_size: int = 2 ** 16, axis: str = "fsdp",
               axis_size: Optional[int] = None) -> Rule:
    """Shard large parameters over the fsdp axis on their largest
    divisible dimension (ZeRO-3-ish; weights all_gather on use,
    grads reduce_scatter — all XLA-inserted).

    Pass ``axis_size`` (the mesh's fsdp extent) to skip dims that don't
    tile; without it the largest dim is chosen and state_shardings'
    divisibility guard may drop the annotation entirely."""

    def rule(path, spec) -> P:
        if math.prod(spec.shape) < min_size:
            return P()
        dims = sorted(range(len(spec.shape)),
                      key=lambda d: -spec.shape[d])
        for d in dims:
            if axis_size is not None and spec.shape[d] % axis_size != 0:
                continue
            parts: list = [None] * len(spec.shape)
            parts[d] = axis
            return P(*parts)
        return P()

    return rule


def tensor_parallel_rules(table: Dict[str, P], default: Rule = None) -> Rule:
    """Explicit per-unit PartitionSpecs, e.g. megatron-style
    ``{"fc1/w": P(None, "model"), "fc2/w": P("model", None)}``."""
    default = default or data_parallel_rules

    def rule(path, spec) -> P:
        key = "/".join(path)
        for pat, pspec in table.items():
            if key == pat or key.endswith("/" + pat):
                return pspec
        return default(path, spec)

    return rule


def compose_rules(*rules: Rule) -> Rule:
    """First rule returning a non-trivial spec wins."""

    def rule(path, spec) -> P:
        for r in rules:
            p = r(path, spec)
            if p != P():
                return p
        return P()

    return rule


def _tree_paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _tree_paths(v, prefix + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _tree_paths(v, prefix + (str(i),))
    else:
        yield prefix, tree


def state_shardings(wstate_spec, mesh: Mesh, rule: Rule = None):
    """Map a rule over the workflow-state pytree -> NamedSharding pytree.
    Scalars (step) and keys are always replicated."""
    rule = rule or data_parallel_rules

    def assign(path, spec):
        shape = getattr(spec, "shape", ())
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        pspec = rule(path, spec)
        # divisibility guard: drop axes that don't tile
        parts = []
        for d, ax in enumerate(tuple(pspec) + (None,) * len(shape)):
            if d >= len(shape):
                break
            if ax is None:
                parts.append(None)
                continue
            ax_size = mesh.shape[ax] if isinstance(ax, str) else math.prod(
                mesh.shape[a] for a in ax)
            parts.append(ax if shape[d] % ax_size == 0 else None)
        while parts and parts[-1] is None:
            parts.pop()
        return NamedSharding(mesh, P(*parts))

    def walk(tree, prefix=()):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + (str(k),)) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, prefix + (str(i),))
                         for i, v in enumerate(tree))
        if isinstance(tree, list):
            return [walk(v, prefix + (str(i),)) for i, v in enumerate(tree)]
        return assign(prefix, tree)

    return walk(wstate_spec)


def batch_shardings(batch_spec, mesh: Mesh, *, seq_axis: Optional[int] = None):
    """Shard every batch array on its leading (batch) axis over
    data×fsdp (fsdp workers are data-parallel too), optionally the sequence
    axis over 'seq'."""
    def assign(spec):
        shape = getattr(spec, "shape", ())
        if len(shape) == 0:
            return NamedSharding(mesh, P())
        parts: list = [None] * len(shape)
        parts[0] = batch_axes(mesh, shape[0])
        if (seq_axis is not None and len(shape) > seq_axis
                and mesh.shape["seq"] > 1
                and shape[seq_axis] % mesh.shape["seq"] == 0):
            parts[seq_axis] = "seq"
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(assign, batch_spec)
