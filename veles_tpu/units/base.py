"""Functional Unit core.

TPU-native re-design of the reference Unit/IUnit dataflow node (reference:
veles/units.py:59,108 — control-flow gate graph run on a thread pool,
``link_from``/``open_gate``/``run_dependent`` :485-554, ``link_attrs``/
``demand`` attribute plumbing :638-682) and the unit registry metaclass
(reference: veles/unit_registry.py:51,178).

The execution model changes completely — this is the core design decision of
the rebuild: a Unit is **pure data + pure functions**, not a live object with
mutable gates.  A unit declares

  * ``inputs``   — names of upstream units whose outputs it consumes
                   (replaces ``link_attrs``; checked at workflow build time,
                   replacing ``demand()``'s runtime None-checks),
  * ``init(key, in_specs)``   — build its parameter/state pytrees,
  * ``apply(params, state, xs, ctx)`` — pure forward computation.

The Workflow (units/workflow.py) topologically sorts units and traces them
into a single XLA computation under ``jax.jit`` — the reference's hot loop
(veles/units.py:782-803, lock-per-unit thread fan-out) disappears into the
compiled program, where XLA schedules operations on the MXU/VPU directly.
Control flow that was data-dependent gating (Decision blocking gradient units
during validation, reference: docs manualrst_veles_units.rst) becomes separate
compiled step functions per phase — see Workflow.train_step/eval_step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..logger import Logger

# A shape/dtype spec for tracing; same role as the reference's demand()-ed
# attribute shapes at initialize() time (veles/workflow.py:303-349).
Spec = jax.ShapeDtypeStruct


def spec_of(x) -> Spec:
    return Spec(jnp.shape(x), jnp.result_type(x))


@dataclasses.dataclass
class Context:
    """Per-call context threaded through apply(): train/eval phase flag and
    a PRNG key (replaces the reference's per-unit reproducible generators,
    veles/units.py:859-885 — keys are split per unit name, so adding units
    never perturbs other units' streams).  ``mesh`` is the device mesh the
    step was compiled under (None on single-device paths) — parallelism-
    aware units (ring attention, pipeline stacks, MoE) read their axis
    sizes off it.

    ``manual_axes`` distinguishes the two collective regimes a unit can
    find itself in.  ``None`` (the default) means ordinary traced code
    under jit: a unit may open its own ``shard_map`` (the ring-attention
    wrapper) or rely on GSPMD sharding propagation.  A tuple means the
    unit is ALREADY executing inside an enclosing ``shard_map`` (a
    pipeline schedule body) where opening another shard_map would
    illegally nest — but raw named-axis collectives (psum / ppermute /
    all_to_all) over the listed axes are legal and the schedule has laid
    the unit's data out for them (round-4 verdict #3: collectives inside
    fused-1F1B stages)."""
    train: bool = True
    key: Optional[jax.Array] = None
    mesh: Optional[Any] = None
    manual_axes: Optional[Tuple[str, ...]] = None

    def axis_size(self, name: str) -> int:
        if self.mesh is None or name not in self.mesh.shape:
            return 1
        return self.mesh.shape[name]

    def collective_mode(self, name: str) -> str:
        """How a unit should parallelize over mesh axis ``name``:
        ``"none"`` (axis absent/size 1, or inside a schedule that has not
        prepared this axis — use the local formulation), ``"wrapper"``
        (ordinary jit — open a shard_map / let GSPMD shard), or
        ``"manual"`` (inside an enclosing shard_map — use raw collectives
        over the named axis)."""
        if self.axis_size(name) <= 1:
            return "none"
        if self.manual_axes is None:
            return "wrapper"
        return "manual" if name in self.manual_axes else "none"

    def unit_key(self, name: str) -> Optional[jax.Array]:
        if self.key is None:
            return None
        # Fold the unit name in deterministically.
        h = 0
        for c in name:
            h = (h * 131 + ord(c)) % (2 ** 31 - 1)
        return jax.random.fold_in(self.key, h)


class UnitRegistry:
    """Name -> class registry for introspection/factories (reference:
    veles/unit_registry.py:51 metaclass; also the UUID factory of libVeles,
    libVeles/inc/veles/unit_factory.h). Used by the export/serving path."""

    _units: Dict[str, type] = {}

    @classmethod
    def register(cls, klass):
        cls._units[klass.__name__] = klass
        return klass

    @classmethod
    def get(cls, name: str) -> type:
        return cls._units[name]

    @classmethod
    def names(cls):
        return sorted(cls._units)


class UnitMeta(type):
    def __init__(cls, name, bases, ns):
        super().__init__(name, bases, ns)
        if name != "Unit":
            UnitRegistry.register(cls)


class Unit(Logger, metaclass=UnitMeta):
    """Base of every dataflow node.

    Subclasses override :meth:`output_spec`, :meth:`init` and :meth:`apply`.
    Units are cheap descriptor objects; all tensors live in the workflow-owned
    state pytree (params/state dicts keyed by unit name), which is what gets
    sharded, donated, and checkpointed.
    """

    #: set by subclasses: does apply() consume a PRNG key when training?
    stochastic: bool = False

    def __init__(self, name: Optional[str] = None,
                 inputs: Sequence[str] = ("@input",)):
        self.name = name or type(self).__name__
        self.inputs: Tuple[str, ...] = tuple(inputs)

    # -- graph wiring (replaces link_from/link_attrs) ----------------------
    def link_from(self, *sources: "Unit | str") -> "Unit":
        """Declare upstream data dependencies. Reference parity:
        veles/units.py:554 link_from + :638 link_attrs collapsed into one
        concept, because in a pure dataflow design control order *is* data
        order."""
        self.inputs = tuple(
            s.name if isinstance(s, Unit) else s for s in sources)
        return self

    # -- functional contract ----------------------------------------------
    def prepare(self, in_specs: Sequence[Spec]) -> None:
        """Build-time hook: called once by Workflow.build with resolved
        input specs, OUTSIDE any jit trace — the place for shape-dependent
        decisions that must not happen during tracing (attention's
        measured pick via runtime.autotune, which times real device
        executions). Default: nothing."""

    def output_spec(self, in_specs: Sequence[Spec]) -> Spec:
        """Shape/dtype inference. Default: identity on the first input."""
        return in_specs[0]

    def init(self, key: jax.Array, in_specs: Sequence[Spec]
             ) -> Tuple[Any, Any]:
        """Return (params, state) pytrees. params are differentiated;
        state is carried across steps (e.g. SOM weights, BN stats)."""
        return {}, {}

    def apply(self, params, state, xs: Sequence[jax.Array], ctx: Context
              ) -> Tuple[jax.Array, Any]:
        """Pure forward: returns (output, new_state)."""
        raise NotImplementedError

    # ----------------------------------------------------------------------
    def __repr__(self):
        return f"{type(self).__name__}({self.name!r} <- {list(self.inputs)})"


class TrivialUnit(Unit):
    """Identity passthrough (reference: veles/units.py:916)."""

    def apply(self, params, state, xs, ctx):
        return xs[0], state


class Forward(Unit):
    """Marker base for trainable forward layers (what the reference calls a
    Znicz forward unit)."""


class LambdaUnit(Unit):
    """Wrap an arbitrary pure function as a unit."""

    def __init__(self, fn: Callable, name=None, inputs=("@input",),
                 out_spec: Optional[Callable] = None):
        super().__init__(name or getattr(fn, "__name__", "LambdaUnit"), inputs)
        self._fn = fn
        self._out_spec = out_spec

    def output_spec(self, in_specs):
        if self._out_spec is not None:
            return self._out_spec(in_specs)
        return jax.eval_shape(lambda *xs: self._fn(*xs), *in_specs)

    def apply(self, params, state, xs, ctx):
        return self._fn(*xs), state


class InputJoiner(Unit):
    """Concatenate inputs along the feature axis (reference:
    veles/input_joiner.py:49 — device-side concat via Jinja-generated
    join.jcl kernel; here a single jnp.concatenate the XLA fuser handles)."""

    def __init__(self, name=None, inputs=(), axis: int = -1):
        super().__init__(name, inputs)
        self.axis = axis

    def output_spec(self, in_specs):
        return jax.eval_shape(
            lambda *xs: jnp.concatenate(xs, axis=self.axis), *in_specs)

    def apply(self, params, state, xs, ctx):
        return jnp.concatenate(xs, axis=self.axis), state


class Avatar(TrivialUnit):
    """Decouples pipelines by cloning a loader output (reference:
    veles/avatar.py:22). In a pure dataflow graph an output can simply be
    consumed twice, so Avatar is an identity kept for graph readability."""
