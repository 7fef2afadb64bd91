"""Device phases of the engine, and the map from compiled op to phase
(DESIGN.md §Observability).

The engine wraps each phase of a solve in ``jax.named_scope``; the scope
lands in the ``op_name`` metadata of every compiled op, so a profiler
trace's ``fusion.N`` can be named by the phase it runs:

    fw/colstats      column statistics (``precompute_colstats``)
    fw/init          warm-start ``X alpha0``, co-state, the lane map of
                     ``solve_batched``, backend padding
    fw/loop          the engine loop: lane bookkeeping, stopping rule,
                     the fused megakernel
    fw/loop/score    inside the loop: vertex sampling and scoring
    fw/loop/update   inside the loop: line search, coefficient and
                     residual update
    fw/gap           the certified duality gap (``certified_gap``)
    fw/result        the rest of the result: alpha, objective, counts

``remember`` is called by the engine's host shim on every dispatch and
keeps the abstract signature (``ShapeDtypeStruct`` leaves, static
arguments as they are) of each distinct call, holding no device buffer.
``op_phases`` runs after the fact: it lowers and compiles each
remembered signature (a compile-cache hit for a program that ran) and
reads ``{op short name: phase}`` off the compiled HLO text.

This module imports nothing of ``repro.core``.
"""
from __future__ import annotations

import functools
import inspect
import re
import threading
from collections import OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import jax

PHASES = ("fw/colstats", "fw/init", "fw/loop", "fw/loop/score",
          "fw/loop/update", "fw/gap", "fw/result")
# a phase inside an op_name path; transforms wrap a scope as ``vmap(fw/...)``
_PHASE = re.compile(r"(?<!\w)(fw/(?:colstats|init|loop|gap|result)"
                    r"(?:/(?:score|update))?)(?![\w])")
# distinct signatures kept per entry, the most recently dispatched last
MAX_SIGNATURES = 8


def scoped(phase: str) -> Callable:
    """Decorator: trace the function inside ``jax.named_scope(phase)``
    (a fresh scope per call, so nested and threaded traces are safe)."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; known: {PHASES}")

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(phase):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase named in an ``op_name`` path, else None:
    ``jit(solve)/fw/result/fw/gap/dot_general`` -> ``fw/gap``,
    ``.../fw/loop/while/body/vmap(fw/loop/score)/gather`` ->
    ``fw/loop/score``."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


# --------------------------------------------------------------------------
# Compiled HLO text -> {op short name: phase}
# --------------------------------------------------------------------------

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s[\w\-]+\((.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(
    r"\b(?:condition|body|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")


class _Instr:
    __slots__ = ("name", "phase", "operands", "calls", "comp")

    def __init__(self, name, phase, operands, calls, comp):
        self.name, self.phase = name, phase
        self.operands, self.calls, self.comp = operands, calls, comp


def _parse(text: str) -> List[_Instr]:
    instrs: List[_Instr] = []
    comp = None
    for line in text.splitlines():
        if comp is None:
            m = _COMP.match(line)
            if m:
                comp = m.group(1)
            continue
        if line.strip() == "}":
            comp = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        # operands are the %names before the first attribute (", x=")
        args = rest.split("), ", 1)[0]
        calls = _CALLS.findall(rest)
        for group in _BRANCHES.findall(rest):
            calls += [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
        instrs.append(_Instr(name, phase_of(op.group(1)) if op else None,
                             _OPERAND.findall(args), calls, comp))
    return instrs


def hlo_phases(text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` for a compiled HLO module's text.

    An instruction whose ``op_name`` names no phase (a copy or a
    parameter the compiler added, a tuple) takes, in order: the phase
    of its first operand that has one, of its first user that has one,
    or of the instruction that calls its computation (a ``while``
    body's ops take the ``while``'s)."""
    instrs = _parse(text)
    by_name = {i.name: i for i in instrs}
    users: Dict[str, List[_Instr]] = {}
    for i in instrs:
        for o in i.operands:
            users.setdefault(o, []).append(i)
    for i in instrs:  # text order: an operand precedes its users
        if i.phase is None:
            i.phase = next((by_name[o].phase for o in i.operands
                            if o in by_name and by_name[o].phase), None)
    for i in reversed(instrs):
        if i.phase is None:
            i.phase = next((u.phase for u in users.get(i.name, ()) if u.phase), None)
    caller = {c: i for i in instrs for c in i.calls}
    for i in instrs:
        comp, seen = i.comp, set()
        while i.phase is None and comp in caller and comp not in seen:
            seen.add(comp)
            i.phase = caller[comp].phase
            comp = caller[comp].comp
    return {i.name: i.phase for i in instrs if i.phase}


# --------------------------------------------------------------------------
# Remembered dispatch signatures
# --------------------------------------------------------------------------

_lock = threading.Lock()
_remembered: Dict[str, "OrderedDict[tuple, tuple]"] = {}
_params: Dict[int, tuple] = {}


def _abstract(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                    sharding=x.sharding)
    t = jax.typeof(x)
    return jax.ShapeDtypeStruct(t.shape, t.dtype, weak_type=t.weak_type)


def remember(entry: str, fn, args: Sequence, kwargs: dict,
             static: Iterable[str]) -> None:
    """Keep the abstract signature of one dispatch ``fn(*args, **kwargs)``
    of the jitted ``fn`` under ``entry``; ``static`` names its static
    arguments, which are kept as they are. A call made while tracing an
    outer program is not kept: it compiles into that program."""
    params = _params.get(id(fn))
    if params is None:
        params = _params[id(fn)] = tuple(inspect.signature(fn).parameters)
    static = frozenset(static)
    fixed = tuple((i, a) for i, (a, n) in enumerate(zip(args, params)) if n in static)
    fixed += tuple((k, v) for k, v in kwargs.items() if k in static)
    dynamic = ([None if n in static else a for a, n in zip(args, params)],
               {k: v for k, v in kwargs.items() if k not in static})
    leaves, treedef = jax.tree_util.tree_flatten(dynamic)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return
    key = (treedef, fixed, tuple(
        (jax.numpy.shape(x), jax.numpy.result_type(x),
         getattr(x, "weak_type", isinstance(x, (int, float, complex))))
        for x in leaves))
    with _lock:
        kept = _remembered.setdefault(entry, OrderedDict())
        if key in kept:
            kept.move_to_end(key)
            return
    call_args, call_kwargs = jax.tree_util.tree_unflatten(
        treedef, [_abstract(x) for x in leaves])
    for k, v in fixed:
        if isinstance(k, int):
            call_args[k] = v
        else:
            call_kwargs[k] = v
    with _lock:
        kept[key] = (fn, tuple(call_args), call_kwargs)
        while len(kept) > MAX_SIGNATURES:
            kept.popitem(last=False)


def forget(entry: Optional[str] = None) -> None:
    """Drop the remembered signatures of ``entry`` (all with None)."""
    with _lock:
        if entry is None:
            _remembered.clear()
        else:
            _remembered.pop(entry, None)


def op_phases(entry: str) -> Dict[str, str]:
    """``{op short name: phase}`` over the programs ``entry`` dispatched:
    each remembered signature is lowered and compiled again (a cache
    hit) and its HLO text read by ``hlo_phases``. Where two programs
    give one name different phases, the more recently dispatched
    program's phase is kept."""
    with _lock:
        calls = list(_remembered.get(entry, {}).values())
    out: Dict[str, str] = {}
    for fn, args, kwargs in calls:
        out.update(hlo_phases(fn.lower(*args, **kwargs).compile().as_text()))
    return out
