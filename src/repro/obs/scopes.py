"""The sweep's phases in the compiled program, by instruction name.

The executors compile each phase of a sweep under a ``jax.named_scope``
named in ``PHASES``; XLA keeps the scope in the ``op_name`` metadata of
every instruction compiled from it.  A profiler trace names a device
operation by its instruction alone, so this module gives out the map
back: the executor step registers its jitted function with the abstract
arguments of its first call (``register``), and ``scope_table()``
compiles each registered program when asked -- after the program has
run, a hit in JAX's in-process or persistent cache -- and reads the map
from its HLO text.

jax is imported lazily, at call time: ``repro.obs`` stays stdlib-only at
import.
"""
from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, NamedTuple, Optional

PHASES = ("ps.pull", "alias.tables", "mh.chain", "ps.push", "ndk.merge")
# the scope, nested in a phase, of the collectives between devices
EXCHANGE = "exchange"
# the entry of a name that two registered programs place in different
# phases: the table never guesses which program ran
AMBIGUOUS = "(ambiguous)"

# an instruction line of HLO text: its name, opcode and what follows
_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (?:.*?[\]}\)]) "
                   r"([a-z][\w\-]*)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
# instructions that only group or route values: they pass no phase on
_STRUCTURAL = ("tuple", "while", "conditional", "call")

_LOCK = threading.Lock()
_PROGRAMS: List[dict] = []   # {"name", "fn", "args", "table", "exchange"}


class Instruction(NamedTuple):
    computation: str
    name: str
    opcode: str
    phase: Optional[str]
    exchange: bool = False


def phase_of(op_name: str) -> Optional[str]:
    """The first of ``PHASES`` that is a component of an ``op_name``
    path, or None."""
    for part in op_name.split("/"):
        if part in PHASES:
            return part
    return None


def _operands(rest: str) -> List[str]:
    """Names in an instruction's operand list (``rest`` follows the
    opcode's opening parenthesis)."""
    depth = 1
    for i, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return _NAME.findall(rest[:i])
    return _NAME.findall(rest)


def in_exchange(op_name: str) -> bool:
    """Whether an ``op_name`` path has ``EXCHANGE`` as a component."""
    return EXCHANGE in op_name.split("/")


def _all(flags) -> bool:
    flags = list(flags)
    return bool(flags) and all(flags)


def _one(phases) -> Optional[str]:
    found = {p for p in phases if p is not None}
    return found.pop() if len(found) == 1 else None


def instructions(hlo_text: str) -> List[Instruction]:
    """Every instruction of an HLO module's text, with its computation,
    opcode and phase.

    XLA keeps ``op_name`` on most instructions but drops it from some it
    creates or rewrites (a scatter fusion, an inserted relayout copy or
    async slice).  An instruction's phase is therefore the phase of its
    own ``op_name``; failing that, the phase of the root of the
    computation it calls (a fusion's, printed before it); failing that,
    the one phase its operands share; failing that, the one phase its
    users share.  Otherwise it has none.  A tuple or control-flow
    instruction takes its own ``op_name``'s phase only, since it gathers
    values of every phase.  Whether an instruction lies under
    ``EXCHANGE`` follows the same rules, where operands or users must all
    lie under it.
    """
    rows = []
    computation = ""
    phase: Dict[str, Optional[str]] = {}     # instruction -> phase
    root: Dict[str, Optional[str]] = {}      # computation -> root's phase
    exch: Dict[str, bool] = {}               # instruction -> under exchange
    root_exch: Dict[str, bool] = {}
    named = set()                            # instructions with an op_name
    users: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name, opcode, rest = m.groups()
        operands = _operands(rest)
        for n in operands:
            users.setdefault(n, []).append(name)
        op = _OP_NAME.search(rest)
        p = phase_of(op.group(1)) if op else None
        e = in_exchange(op.group(1)) if op else False
        if op:
            named.add(name)
        elif opcode not in _STRUCTURAL:
            called = _CALLS.findall(rest)
            e = (_all(root_exch.get(c, False) for c in called)
                 or _all(exch.get(n, False) for n in operands))
        if p is None and opcode not in _STRUCTURAL:
            p = (_one(root.get(c) for c in _CALLS.findall(rest))
                 or _one(phase.get(n) for n in operands))
        phase[name] = p
        exch[name] = e
        if line.lstrip().startswith("ROOT"):
            root[computation] = p
            root_exch[computation] = e
        rows.append((computation, name, opcode))
    for _, name, opcode in reversed(rows):
        if opcode in _STRUCTURAL:
            continue
        if phase[name] is None:
            phase[name] = _one(phase[u] for u in users.get(name, ()))
        if not exch[name] and name not in named:
            exch[name] = _all(exch[u] for u in users.get(name, ()))
    return [Instruction(c, n, o, phase[n], exch[n]) for c, n, o in rows]


def _abstract(x: Any) -> Any:
    import jax
    if not isinstance(x, jax.Array):
        return x
    # an uncommitted argument lowers with no sharding; matching the call
    # exactly lets the compile reuse the executable that ran
    sharding = x.sharding if getattr(x, "_committed", True) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def register(name: str, fn: Any, args: tuple) -> None:
    """Keep jitted ``fn`` under ``name`` with the abstract shapes and
    shardings of ``args`` (one call's arguments; no buffer is kept)."""
    import jax
    entry = {"name": name, "fn": fn,
             "args": jax.tree.map(_abstract, args), "table": None,
             "exchange": None}
    with _LOCK:
        _PROGRAMS.append(entry)


def registered() -> List[str]:
    with _LOCK:
        return [p["name"] for p in _PROGRAMS]


def scope_table(exchange: bool = False) -> Dict[str, Any]:
    """``{instruction name: phase or None}`` over every registered
    program, each compiled (once) on the first call that needs it; with
    ``exchange``, ``{instruction name: (phase or None, under
    EXCHANGE)}``.  A name that two programs place differently maps to
    ``AMBIGUOUS``."""
    with _LOCK:
        programs = list(_PROGRAMS)
    table: Dict[str, Any] = {}
    for p in programs:
        if p["table"] is None:
            text = p["fn"].lower(*p["args"]).compile().as_text()
            rows = instructions(text)
            p["exchange"] = {i.name for i in rows if i.exchange}
            p["table"] = {i.name: i.phase for i in rows}
        under = p.get("exchange") or ()
        for name, entry in p["table"].items():
            if exchange:
                entry = (entry, name in under)
            if table.setdefault(name, entry) != entry:
                table[name] = AMBIGUOUS
    return table
