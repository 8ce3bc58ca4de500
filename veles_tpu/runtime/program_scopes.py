"""Which unit each instruction of a compiled step program belongs to,
and from that the device's seconds by unit.

The program scopes what it traces: ``Workflow.forward`` puts
``jax.named_scope(u.name)`` around every unit, ``_build_step`` puts
``optimizer`` around the update, the units put sub-scopes inside
(``ssm_scan``, ``moe_dispatch``, ``gdn_scan/gdn_chunk``), and autodiff
wraps them: ``jvp(<unit>)`` forward, ``transpose(jvp(<unit>))``
backward.  XLA carries all of it to the optimised module as each
instruction's ``metadata={op_name="jit(step)/transpose(jvp(b3_mix))/
ssm_scan/ssd_carry/mul"}``.  A device trace shows none of it: an
event's name is the instruction's printed text, without ``metadata=``
and ``backend_config=``.  So the program notes, where it compiles
(:meth:`~veles_tpu.runtime.step_cache.StepCache.get_step`), a **scope
table** of every compiled program, read from ``compiled.as_text()``,
and :func:`seconds_by_scope` joins a trace's seconds by event name to
it.  Three rules:

* **A fusion goes whole to the scope XLA names it by**, as the trace's
  own ``tf_op`` stat would.  XLA fuses across units: AlexNet's
  ``fusion.275`` is LRN1's backward with conv1's ReLU mask, its bias
  gradient and a bfloat16 cast, and all of it is charged to the one
  unit in the fusion's ``op_name``.  A unit's seconds here are the
  seconds of the instructions named after it, not of its arithmetic.
  A fusion XLA gave no ``op_name`` (a piece of a ``concatenate`` it
  rewrote to updates in place, a root it made itself) takes the scope
  that the instructions fused into it agree on (:func:`_agreed`).
* **Self time.**  A ``conditional``, ``while`` or ``call`` event lasts
  as long as the instructions of the computations it calls, which are
  events of their own inside it.  It is charged its seconds less
  theirs (one level: a ``while`` inside a ``conditional`` does the same
  in turn), so the rows sum to the device's busy seconds and a routed
  layer's experts are not counted twice.
* **The whole printed text decides.**  Instruction names repeat
  between programs (``%flash_fwd.7`` is in the train step and in the
  validation step, under different operands), so an event is matched by
  its whole text and by its name only where one noted program has it.
  What two programs claim under different units is ``ambiguous``; what
  no noted program has is ``unmatched`` (a program that was not
  compiled through :func:`note`).

The tables are plain host data: strings and tuples, no reference to a
``Compiled`` object or a device buffer.  They live in a process-wide
bounded store beside the span ring (:func:`noted`), because a trainer
and its ``StepCache`` may be gone before anyone reads a trace.
"""

from __future__ import annotations

import re
import threading
import time
from collections import OrderedDict
from typing import (Any, Dict, Iterable, List, Mapping, NamedTuple,
                    Optional, Sequence, Tuple)

#: scopes of the program itself that stand where a unit's name would
PROGRAM_SCOPES = ("optimizer", "loader_gather", "loader_aug")
UNSCOPED, AMBIGUOUS, UNMATCHED = "unscoped", "ambiguous", "unmatched"
FORWARD, BACKWARD = "forward", "backward"
#: the newest table of each (program kind, module name), at most
MAX_NOTED = 32
#: how many instructions a total without a unit keeps by name
HEAVIEST = 5

#: components of an ``op_name`` that jax's own machinery adds between
#: the program's scopes: they name no scope of the program
_MACHINERY = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "rematted_computation", "remat", "remat2", "pjit", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_lin", "scan",
    "shard_map", "pallas_call"))
_BRANCH = re.compile(r"branch_\d+_fun$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
#: opcodes whose called computations' instructions are events of their
#: own inside the caller's event; a ``fusion`` is one event and the
#: computations a ``reduce`` or ``sort`` applies never run alone
_CALLERS = re.compile(
    r"\b(body|condition|true_computation|false_computation|to_apply|calls)"
    r"=(%?[\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_METADATA = re.compile(r', metadata=\{(?:[^{}"]|"[^"]*")*\}')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%?[\w.\-]+) = ")
_TUPLE_END = re.compile(r"\) ([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%[\w.\-]+")
_INDEX = re.compile(r"/\*index=\d+\*/")
_COMPUTATION = re.compile(r"^(ENTRY )?(%?[\w.\-]+) .*\{$")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_NO_VOTE = ("constant", "broadcast")    # see _agreed


class Instruction(NamedTuple):
    """One instruction that can be an event of a device trace."""
    name: str               # "%fusion.829"
    text: str               # as the trace names its event
    computation: str
    opcode: str
    calls: Tuple[str, ...]  # computations whose instructions run inside
    unit: Optional[str]     # None: unscoped
    path: str               # "ssm_scan/ssd_carry"
    direction: str          # forward / backward; "" where unscoped
    recomputed: bool        # a forward computed again inside a backward


def _rescoped(ins: Instruction, scope: Tuple) -> Instruction:
    """``ins`` under another ``(unit, path, direction, recomputed)``."""
    return Instruction(*ins[:5], *scope)


class ScopeTable:
    """The instructions of one compiled program with their scopes, and
    what the trainer told about its units: ``units`` maps a unit's name
    to its class's name, ``evaluator`` and ``head`` name the unit that
    computes the loss and the one that feeds it."""

    def __init__(self, program: str, module: str,
                 instructions: Sequence[Instruction],
                 units: Optional[Mapping[str, str]] = None,
                 evaluator: Optional[str] = None,
                 head: Optional[str] = None):
        self.program = program
        self.module = module
        self.instructions = tuple(instructions)
        self.units = dict(units or {})
        self.evaluator = evaluator
        self.head = head
        self.by_text = {i.text: i for i in self.instructions}
        self.by_name = {i.name: i for i in self.instructions}

    @property
    def scoped(self) -> int:
        return sum(1 for i in self.instructions if i.unit is not None)

    def klass(self, unit: Optional[str]) -> Optional[str]:
        """The class of a unit, and for a scope of the program its own
        name (``optimizer``)."""
        if unit in PROGRAM_SCOPES:
            return unit
        return self.units.get(unit)

    def role(self, unit: Optional[str]) -> Optional[str]:
        """``evaluator`` for the unit that computes the loss, ``head``
        for the one that feeds it, None for any other."""
        if unit is None:
            return None
        return {self.evaluator: "evaluator", self.head: "head"}.get(unit)

    def to_json(self) -> Dict[str, Any]:
        return {"program": self.program, "module": self.module,
                "units": self.units, "evaluator": self.evaluator,
                "head": self.head,
                "instructions": [list(i) for i in self.instructions]}

    @classmethod
    def from_json(cls, d: Mapping[str, Any]) -> "ScopeTable":
        return cls(d["program"], d["module"],
                   [Instruction(*(tuple(v) if isinstance(v, list) else v
                                  for v in row))
                    for row in d["instructions"]],
                   d.get("units"), d.get("evaluator"), d.get("head"))


# -- from an ``op_name`` to a scope -----------------------------------------

def scope_of(op_name: str, units: Iterable[str] = ()
             ) -> Tuple[Optional[str], str, str, bool]:
    """``(unit, path, direction, recomputed)`` of one ``op_name``.

    ``unit`` is the first component that, with its ``jvp(`` /
    ``transpose(`` / ``vmap(`` wrappers peeled, names one of ``units`` or
    a scope of the program (``optimizer``, ``loader_gather``,
    ``loader_aug``); None where none does.  ``path`` is the program's
    named scopes below it: the components that are neither a function
    (``jit(silu)``), nor jax's machinery (``while/body``, ``checkpoint``,
    ``cond/branch_1_fun``), nor an einsum's formula, nor the unit's name
    again, nor the last one, which is the primitive's.  ``direction`` is
    ``backward`` under any ``transpose(``, else ``forward``;
    ``recomputed`` where a backward instruction sits under
    ``rematted_computation``."""
    names = set(units) | set(PROGRAM_SCOPES)
    # XLA joins the names of instructions it merged with ";"
    parts = op_name.split(";", 1)[0].split("/")
    backward = recomputed = False
    scopes: List[Optional[str]] = []
    for part in parts:
        function = False
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            wrapper, part = m.group(1), m.group(2)
            backward = backward or wrapper == "transpose"
            function = function or wrapper in ("jit", "pjit")
        recomputed = recomputed or part == "rematted_computation"
        scopes.append(None if function else part)
    unit, at = None, -1
    for at, part in enumerate(scopes):
        if part in names:
            unit = part
            break
    if unit is None:
        return None, "", "", False
    path: List[str] = []
    for part in scopes[at + 1:-1]:
        if part is None or part == unit or part in _MACHINERY \
                or part in path or "->" in part or _BRANCH.match(part):
            continue
        path.append(part)
    return (unit, "/".join(path), BACKWARD if backward else FORWARD,
            backward and recomputed)


# -- from a module's text to a table ----------------------------------------

def _shown(rest: str) -> str:
    """What follows ``%name = `` as a device trace shows it: without
    ``metadata={...}`` and ``backend_config=...`` (XLA prints that
    last)."""
    if ", metadata={" in rest:
        rest = _METADATA.sub("", rest, count=1)
    cut = rest.find(", backend_config=")
    return rest if cut < 0 else rest[:cut]


def _split(rest: str) -> Tuple[str, str, str]:
    """``(type, opcode, what follows the opcode's parenthesis)`` of
    ``<type> <opcode>(<operands>)...``.  A tuple's type is in parentheses
    and holds spaces, but ``) word(`` only where it ends; any other type
    holds no space."""
    if rest.startswith("("):
        m = _TUPLE_END.search(rest)
        if m is None:
            return rest, "", ""
        return rest[:m.start() + 1], m.group(1), rest[m.end():]
    kind, _, rest = rest.partition(" ")
    opcode, _, after = rest.lstrip().partition("(")
    return kind, opcode, after


def _called(attributes: str, opcode: str) -> Tuple[str, ...]:
    """The computations whose instructions run, as events of their own,
    inside this instruction's event."""
    if opcode == "fusion" or "=" not in attributes:
        return ()
    out = []
    for key, one, many in _CALLERS.findall(attributes):
        if key == "to_apply" and opcode != "call":
            continue        # what a reduce or a sort applies
        out += [one] if one else [c.strip() for c in many.split(",")]
    return tuple(c.lstrip("%") for c in out if c)


def _agreed(votes: Iterable[Tuple[str, Tuple]]
            ) -> Tuple[Optional[str], str, str, bool]:
    """The scope of an instruction XLA left without an ``op_name``, from
    the ``(opcode, (unit, path, direction, recomputed))`` of what runs
    inside it: the unit that all of them name that do work, if they name
    one, and the path that those of them with a path agree on.  A
    ``constant`` and a ``broadcast`` have no vote: XLA shares a constant
    between the fusions of several units and leaves the first unit's
    name on it and on what spreads it."""
    scoped = [scope for opcode, scope in votes
              if scope[0] is not None and opcode not in _NO_VOTE]
    if not scoped or len({s[0] for s in scoped}) > 1:
        return None, "", "", False
    paths = {s[1] for s in scoped if s[1]}
    backward = any(s[2] == BACKWARD for s in scoped)
    return (scoped[0][0], paths.pop() if len(paths) == 1 else "",
            BACKWARD if backward else FORWARD, False)


def _fused(lines: Iterable[str]) -> Iterable[Tuple[str, str]]:
    """``(opcode, op_name)`` of the named instructions of a fusion's
    computation."""
    for line in lines:
        m, named = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if m is not None and named is not None:
            yield _split(line[m.end():])[1], named.group(1)


def parse(program: str, text: str, units: Optional[Mapping[str, str]] = None,
          evaluator: Optional[str] = None, head: Optional[str] = None
          ) -> ScopeTable:
    """The scope table of one module as ``Compiled.as_text()`` prints
    it.  Kept are the instructions that can be events: those of the
    entry computation and of every computation reached from it through
    a ``while``, a ``conditional``, a ``call`` or an asynchronous
    wrapper.  A fusion's computation is not entered (the fusion is the
    event), nor what a ``reduce``, ``sort`` or ``scatter`` applies.

    ``as_text()`` prints an operand by its name; a trace's event names
    it with its type before (``fusion(f32[8]{0} %w)``).  The types are
    the operands' own result types, in the same computation, so the
    table's text puts them in.  A ``conditional`` or ``while`` that XLA
    left without an ``op_name`` takes the unit that the instructions it
    calls agree on, a fusion without one the unit that the instructions
    fused into it agree on (:func:`_agreed`), and an instruction without
    one inside a called computation takes its caller's scope: it runs
    inside the caller's event."""
    module, entry, computation = "", None, None
    lines: Dict[str, List[str]] = {}
    body: List[str] = []
    for line in text.splitlines():
        if line.startswith("  "):
            body.append(line)       # most are a fusion's: read if kept
            continue
        m = _COMPUTATION.match(line)
        if m is not None:
            computation = m.group(2).lstrip("%")
            body = lines[computation] = []
            if m.group(1):
                entry = computation
        elif line.startswith("HloModule"):
            m = _MODULE.match(line)
            module = m.group(1) if m else ""
    names = tuple(units or ())
    scopes: Dict[str, Tuple] = {}       # many instructions share a name

    def scoped(op_name):
        scope = scopes.get(op_name)
        if scope is None:
            scope = scopes[op_name] = scope_of(op_name, names)
        return scope

    kept: Dict[str, List[Instruction]] = {}
    queue = [entry] if entry is not None else []
    while queue:
        computation = queue.pop()
        if computation in kept or computation not in lines:
            continue
        types: Dict[str, str] = {}
        rows = []
        for line in lines[computation]:
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            name = m.group(1)
            rest = _shown(line[m.end():])
            types[name], opcode, after = _split(rest)
            rows.append((name, line, rest, opcode, after))
        kept[computation] = []
        for name, line, rest, opcode, after in rows:
            operands, closed, attributes = after.partition(")")
            if "%" in operands:
                # every fifth operand is numbered in a comment here, and
                # in a trace only inside an operand's tuple type
                operands = _OPERAND.sub(
                    lambda m: f"{types[m.group(0)]} {m.group(0)}"
                    if m.group(0) in types else m.group(0),
                    _INDEX.sub("", operands))
                rest = (rest[:len(rest) - len(after)] + operands + closed
                        + attributes)
            calls = _called(attributes, opcode)
            queue.extend(calls)
            m = _OP_NAME.search(line)
            scope = scoped(m.group(1) if m else "")
            if scope[0] is None and opcode == "fusion":
                # the pieces of a concatenate that XLA rewrites to updates
                # in place carry no metadata but the last: such a fusion
                # takes the scope the instructions fused into it agree on
                fused = _CALLERS.search(attributes)     # its one calls=
                if fused is not None and fused.group(2):
                    scope = _agreed(
                        (opcode, scoped(n)) for opcode, n in _fused(
                            lines.get(fused.group(2).lstrip("%"), ())))
            kept[computation].append(Instruction(
                name, f"{name} = {rest}", computation, opcode, calls,
                *scope))
    # a caller XLA left unnamed takes the unit its callees agree on
    # (callees were discovered after their callers: deepest first) ...
    for computation in reversed(list(kept)):
        kept[computation] = [
            _rescoped(ins, _agreed((i.opcode, i[5:]) for c in ins.calls
                                   for i in kept.get(c, ())))
            if ins.unit is None and ins.calls else ins
            for ins in kept[computation]]
    # ... and what XLA left unnamed inside a called computation (the
    # loops its expanders write carry no metadata) runs inside its
    # caller's event and takes the caller's scope, callers first
    for computation in list(kept):
        for caller in kept[computation]:
            if caller.unit is None:
                continue
            for c in caller.calls:
                kept[c] = [_rescoped(i, caller[5:]) if i.unit is None else i
                           for i in kept.get(c, ())]
    instructions = [ins for rows in kept.values() for ins in rows]
    return ScopeTable(program, module, instructions, units, evaluator, head)


# -- the store ---------------------------------------------------------------

_LOCK = threading.Lock()
_NOTED: "OrderedDict[Tuple[str, str], ScopeTable]" = OrderedDict()


def note(program: str, text: str, units: Optional[Mapping[str, Any]] = None
         ) -> ScopeTable:
    """Parse a compiled program's text and keep its table: the newest
    for each (program kind, module name), at most :data:`MAX_NOTED`.
    ``units`` is what :func:`workflow_units` gives, or None for a
    program of no workflow."""
    units = units or {}
    table = parse(program, text, units.get("classes"),
                  units.get("evaluator"), units.get("head"))
    with _LOCK:
        _NOTED.pop((program, table.module), None)
        _NOTED[(program, table.module)] = table
        while len(_NOTED) > MAX_NOTED:
            _NOTED.popitem(last=False)
    return table


def note_compiled(program: str, compiled, units=None, into=None
                  ) -> Optional[ScopeTable]:
    """:func:`note` on a ``jax.stages.Compiled``, best effort (a backend
    that prints no text notes nothing).  ``into``, a span's ``args``,
    gains ``instructions``, ``scoped`` and ``noting_s``."""
    t0 = time.perf_counter()
    try:
        table = note(program, compiled.as_text(), units)
    except Exception:  # observability must not fail a compile
        return None
    if into is not None:
        into.update(instructions=len(table.instructions),
                    scoped=table.scoped,
                    noting_s=round(time.perf_counter() - t0, 4))
    return table


def noted() -> List[ScopeTable]:
    """The noted tables, oldest first."""
    with _LOCK:
        return list(_NOTED.values())


def clear() -> None:
    with _LOCK:
        _NOTED.clear()


def workflow_units(workflow) -> Dict[str, Any]:
    """What a trainer hands along with its programs: each unit's class
    by name, which unit is the evaluator and which feeds it (the
    head), so a reader can ask for every ``Mamba2Mixer`` without knowing
    a configuration's names."""
    evaluator = getattr(workflow, "evaluator", None)
    classes = {u.name: type(u).__name__ for u in workflow.units}
    head = next((s for s in getattr(evaluator, "inputs", ())
                 if s in classes), None)
    return {"classes": classes,
            "evaluator": getattr(evaluator, "name", None), "head": head}


# -- the join ----------------------------------------------------------------

def _head(text: str) -> str:
    """``%name = <type> <opcode>``: an event's text up to its operands."""
    name, _, rest = text.partition(" = ")
    kind, opcode, _ = _split(rest)
    return f"{name} = {kind} {opcode}"


def _claims(text: str, tables: Sequence[ScopeTable]
            ) -> List[Tuple[ScopeTable, Instruction]]:
    """The (table, instruction) pairs that can be the event ``text``: by
    the whole text, and where no program has that, by the name of an
    instruction of the same opcode and type (two programs number their
    fusions alike; a program that was not noted must not be read into
    one that was)."""
    found = [(t, t.by_text[text]) for t in tables if text in t.by_text]
    if not found:
        name = text.partition(" = ")[0]
        found = [(t, t.by_name[name]) for t in tables if name in t.by_name]
        if found:
            head = _head(text)
            found = [(t, i) for t, i in found if _head(i.text) == head]
    return found


def seconds_by_scope(seconds_by_event_name: Mapping[str, float],
                     tables: Optional[Sequence[ScopeTable]] = None
                     ) -> Dict[str, Any]:
    """Join a device trace's seconds by event name (what the benchmark's
    ``trace_reduce.reduce`` returns as ``seconds_by_op``) to the noted
    tables.  Returns::

        {"rows": [{"program", "unit", "class", "role", "path",
                   "direction", "seconds", "recomputed_s"}, ...],
                                                       # heaviest first
         "unscoped" | "ambiguous" | "unmatched":
             {"seconds": s, "heaviest": [[event text, seconds], ...]},
         "scoped_s": the rows' sum, "total_s": rows and the three}

    An event is its instruction's by the rules of this module's
    docstring; one that several programs claim under one unit goes to
    the first of them that was noted (a trace sums such an event's
    seconds over the programs, and they cannot be told apart again).
    Events that call computations are charged their self time: their
    seconds less those of every event that any claim places in a
    computation they call, never less than nothing.  So ``total_s`` is
    the device's busy seconds where operations do not overlap."""
    tables = noted() if tables is None else list(tables)
    resolved: List[Tuple[ScopeTable, Instruction, str, float]] = []
    loose = {UNSCOPED: [], AMBIGUOUS: [], UNMATCHED: []}
    inside: Dict[Tuple[int, str], float] = {}
    for text, seconds in seconds_by_event_name.items():
        claims = _claims(text, tables)
        if not claims:
            loose[UNMATCHED].append((text, seconds))
        elif len({i.unit for _, i in claims}) > 1:
            loose[AMBIGUOUS].append((text, seconds))
        else:
            resolved.append((*claims[0], text, seconds))
        for table, ins in claims:
            key = (id(table), ins.computation)
            inside[key] = inside.get(key, 0.0) + seconds
    rows: Dict[Tuple, Dict[str, Any]] = {}
    for table, ins, text, seconds in resolved:
        if ins.calls:
            seconds = max(0.0, seconds - sum(
                inside.get((id(table), c), 0.0) for c in ins.calls))
        if ins.unit is None:
            loose[UNSCOPED].append((text, seconds))
            continue
        key = (table.program, ins.unit, ins.path, ins.direction)
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                "program": table.program, "unit": ins.unit,
                "class": table.klass(ins.unit),
                "role": table.role(ins.unit), "path": ins.path,
                "direction": ins.direction, "seconds": 0.0,
                "recomputed_s": 0.0}
        row["seconds"] += seconds
        if ins.recomputed:
            row["recomputed_s"] += seconds
    out: Dict[str, Any] = {
        "rows": sorted(rows.values(), key=lambda r: -r["seconds"])}
    out["scoped_s"] = sum(r["seconds"] for r in out["rows"])
    out["total_s"] = out["scoped_s"]
    for kind, events in loose.items():
        events.sort(key=lambda e: -e[1])
        seconds = sum(s for _, s in events)
        out[kind] = {"seconds": seconds,
                     "heaviest": [list(e) for e in events[:HEAVIEST]]}
        out["total_s"] += seconds
    return out
