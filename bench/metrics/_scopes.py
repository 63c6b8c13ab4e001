"""Device time of the sweep program by the program's named scopes.

The scan engine names the parts of its interval body with
``jax.named_scope`` (``synth``, ``sample``, ``policy``, ``migrate``,
``account``), and a union member's ops read ``policy/<family>``.  A
scope reaches the compiled module's ``op_name`` metadata, but not the
TPU trace, whose op events are named by their HLO instruction
(``%fusion.21 = ...``) and carry no ``op_name``.  So the map from
instruction to scope comes from the compiled module's text: the cell's
sweep is built again, its call of ``scan_engine._sim_synth_jit`` is
caught before it runs, and that call is lowered and compiled.  JAX's
in-memory caches hold the executable the window ran, so nothing
compiles again, and the instruction names are the trace's.
"""
from __future__ import annotations

import bisect
import collections
import re

#: a computation's first line, and an instruction of the module's text
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT )?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
#: op_name components of the control structure, not of the work
_STRUCTURE = re.compile(
    r"^(jit\(.*\)|while|body|cond|closed_call|branch_\d+_fun|vmap\(\))$")
#: a scope opened inside ``jax.vmap`` reads ``vmap(<scope>)``
_VMAP = re.compile(r"^vmap\((.*)\)$")
MODULE = "_sim_synth_jit"


class _Caught(Exception):
    """Raised in place of the sweep program's call once its arguments
    are caught."""


def module_text(cell) -> str:
    """The compiled text of the cell's sweep program, or "" where the
    program has no ``scan_engine._sim_synth_jit`` or the sweep does not
    call it."""
    from repro.simulator import experiment, scan_engine
    jitted = getattr(scan_engine, MODULE, None)
    if jitted is None:
        return ""
    caught = []

    def catch(*a, **kw):
        caught.append((a, kw))
        raise _Caught

    setattr(scan_engine, MODULE, catch)
    try:
        experiment.sweep(**cell.sweep_args(0))
    except _Caught:
        pass
    finally:
        setattr(scan_engine, MODULE, jitted)
    if not caught:
        return ""
    a, kw = caught[0]
    return jitted.lower(*a, **kw).compile().as_text()


def op_names(text: str) -> dict:
    """Instruction name -> op_name, from a compiled module's text."""
    return {name: on for name, (on, _) in resolve(text).items()}


def resolve(text: str) -> dict:
    """Instruction name -> (op_name, how it was found).

    ``how`` is ``"own"`` where the instruction's metadata names more than
    the loop structure.  Passes of the TPU compiler leave some
    instructions without metadata (a scatter's fusion, a sort's
    rewrite), or with only the scan body's (``.../while/body/
    closed_call``).  Such an instruction takes, first, the op_name of
    the computations it calls (their root first, then nested calls):
    ``how`` is ``"called"``.  Failing that, the nearest informative
    op_name breadth first through its users and operands (and what those
    call): ``how`` is the path's edges, such as ``"operand"`` or
    ``"user>operand"``.  Where nothing informative is reached the own
    op_name stays, with ``how`` ``"none"``."""
    own, body = {}, {}
    calls, users, operands = (collections.defaultdict(list)
                              for _ in range(3))
    comp = None
    for line in text.split("\n"):
        m = _COMP.match(line)
        if m:
            comp = body.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        root, name, rest = m.groups()
        on = _OP_NAME.search(rest)
        own[name] = on.group(1) if on else ""
        comp.insert(0, name) if root else comp.append(name)
        calls[name] = _CALLS.findall(rest)
        for r in _REF.findall(rest.split(", metadata=")[0]):
            operands[name].append(r)
            users[r].append(name)

    def called(x):
        """The instructions of the computations ``x`` calls, roots
        first."""
        return [i for c in calls[x] for i in body.get(c, ())]

    def inside(name):
        seen, level = {name}, [name]
        while level:
            nxt = []
            for x in level:
                for z in called(x):
                    if z in seen:
                        continue
                    if _informative(own[z]):
                        return own[z]
                    seen.add(z)
                    nxt.append(z)
            level = nxt
        return None

    def nearest(name):
        seen, level = {name}, [(name, "")]
        while level:
            nxt = []
            for x, path in level:
                for z, edge in ([(i, "called") for i in called(x)]
                                + [(u, "user") for u in users[x]]
                                + [(o, "operand") for o in operands[x]]):
                    if z in seen or z not in own:
                        continue
                    via = f"{path}>{edge}" if path else edge
                    if _informative(own[z]):
                        return own[z], via
                    seen.add(z)
                    nxt.append((z, via))
            level = nxt
        return own[name], "none"

    out = {}
    for name, on in own.items():
        if _informative(on):
            out[name] = (on, "own")
        elif (inner := inside(name)) is not None:
            out[name] = (inner, "called")
        else:
            out[name] = nearest(name)
    return out


def _informative(op_name: str) -> bool:
    """Whether an op_name names more than the module's control
    structure (``jit(f)/while/body/closed_call/cond/...``)."""
    return any(not _STRUCTURE.match(c) for c in op_name.split("/")) \
        if op_name else False


def parts(op_name: str) -> list:
    """The components of an op_name path, ``vmap(x)`` read as ``x``."""
    out = []
    for c in op_name.split("/"):
        m = _VMAP.match(c)
        while m:
            c = m.group(1)
            m = _VMAP.match(c)
        out.append(c)
    return out


def in_scope(op_name: str, segment: str) -> bool:
    """Whether the components of ``segment`` (``policy`` or
    ``policy/hemem``) appear, in order, among the op_name's."""
    it = iter(parts(op_name))
    return all(s in it for s in segment.split("/"))


def program_self_ns(trace) -> collections.Counter:
    """Self nanoseconds by instruction name of the ops that ran inside
    the sweep program's module events, in the traced window."""
    lo, hi = trace.window
    out = collections.Counter()
    for dev, ops in trace.ops.items():
        mods = sorted((m.start, m.end) for m in trace.modules.get(dev, ())
                      if MODULE in m.name)
        starts = [s for s, _ in mods]
        for o in ops:
            if not (lo <= o.start and o.end <= hi):
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.end <= mods[i][1]:
                out[o.name.split(" = ", 1)[0].lstrip("%")] += o.self_ns
    return out


def scope_self_s(ctx, segment: str) -> float:
    """Self seconds of the sweep program's ops under ``segment``; the
    instruction map and the self times are built once per run and kept
    in ``ctx``."""
    if "op_names" not in ctx:
        ctx["op_names"] = op_names(module_text(ctx["cell"]))
    if "program_self_ns" not in ctx:
        ctx["program_self_ns"] = program_self_ns(ctx["trace"])
    names = ctx["op_names"]
    return sum(ns for instr, ns in ctx["program_self_ns"].items()
               if in_scope(names.get(instr, ""), segment)) / 1e9


def share(ctx, segment: str) -> float | None:
    """``segment``'s self time over the device-busy time, in %; None
    where the program has no such scope, or where an instruction that ran
    in the window is missing from the compiled text (the window ran
    another executable than the one built again, so no op can be trusted
    to its scope)."""
    busy = ctx["trace"].busy_s()
    if busy <= 0:
        return None
    secs = scope_self_s(ctx, segment)
    if not ctx["program_self_ns"].keys() <= ctx["op_names"].keys():
        return None
    return 100.0 * secs / busy if secs > 0 else None
