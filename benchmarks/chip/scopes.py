"""Device time by the program's named scopes.

The step programs name their parts with ``jax.named_scope`` (``forward``,
``optimizer``, ``attn``, ``moe``, ``grad_sync/bucket_<i>``...), which XLA
keeps as each HLO instruction's ``op_name`` metadata.  A TPU trace names an
op by its instruction only, so the scope of a traced op comes from the
compiled program's text: ``scope_map`` joins instruction names to
``op_name``s, and ``split`` sums the traced ops' self time by part.

The compiled text comes from the session's ``programs`` (each step program
compiled ahead of its first call), so reading it compiles nothing.  A
program without that attribute, or whose ops carry no scopes, gives no
split (``None``): every reader of a part then reports nothing.

Instruction names are unique within one program; a small program run
beside the step (the rng's ``fold_in``) may reuse a name, and its op then
counts under the step's scope: a few microseconds a step.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Set, Tuple

import tracefile as tr

COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation|branch_computations)=(\{[^}]*\}|%[\w.\-]+)")
WRAPPER = re.compile(r"[\w.\-]+\((.*)\)")

# the parts a split reports, each a test on an op_name
PARTS = {
    "forward": lambda n, s: "forward" in s and "transpose(" not in n,
    "backward": lambda n, s: "transpose(" in n,
    "optimizer": lambda n, s: "optimizer" in s,
    "attn": lambda n, s: "attn" in s,
    "moe": lambda n, s: "moe" in s,
    "grad_sync": lambda n, s: "grad_sync" in s,
}
# the opcodes of collectives, with their async ``-start``/``-done`` halves
# (an instruction's name need not say it: ``psum.229 = ... all-reduce(``)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OPCODE = re.compile(r"\s([\w\-]+)\(")


def scope_map(text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` of its scope, for every
    instruction of a compiled HLO module's text.  An instruction without
    a scoped ``op_name`` takes that of the computation it calls (a fusion's root, or
    its first instruction that has one, nested fusions followed down), or
    else that of the instruction that calls its own computation (a
    ``while`` body's copies take the loop's)."""
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    calls: Dict[str, list] = {}
    members: Dict[str, list] = {}
    root: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m and comp is not None:
            name = m.group(1)
            op = OP_NAME.search(line)
            # a bare name ("add", "scatter") is what a compiler pass left on
            # an instruction it made: no scope, so look further
            own[name] = op.group(1) if op and "/" in op.group(1) else None
            comp_of[name] = comp
            members.setdefault(comp, []).append(name)
            if line.lstrip().startswith("ROOT "):
                root[comp] = name
            calls[name] = [c.strip(" {}%") for m2 in CALLED.finditer(line)
                           for c in m2.group(1).split(",")
                           if c.strip(" {}%")]
            for c in calls[name]:
                caller.setdefault(c, name)
            continue
        m = COMPUTATION.match(line)
        if m and not line.startswith(" "):
            comp = m.group(1)
        elif line.startswith("}"):
            comp = None

    def down(name: str, seen: set) -> Optional[str]:
        """The scope found in ``name`` or what it calls."""
        if own.get(name) or name in seen:
            return own.get(name)
        seen.add(name)
        for c in calls.get(name, ()):
            order = [root[c]] if c in root else []
            for inner in order + members.get(c, []):
                found = down(inner, seen)
                if found:
                    return found
        return None

    out: Dict[str, str] = {}
    for name in own:
        found, up, hops = down(name, set()), name, 0
        while found is None and hops < 32:
            up = caller.get(comp_of.get(up, ""))
            if up is None:
                break
            found, hops = down(up, set()), hops + 1
        if found is not None:
            out[name] = found
    return out


def collective_names(text: str) -> Set[str]:
    """Names of the instructions of a compiled HLO module's text whose
    opcode is a collective."""
    out = set()
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OPCODE.search(" " + line[m.end():].split(" metadata=")[0])
            if op and op.group(1).startswith(COLLECTIVES):
                out.add(m.group(1))
    return out


def scope_names(op_name: str) -> Set[str]:
    """The scope names on an op's name stack, with JAX's transform
    wrappers taken off (``jvp(embed)`` and ``transpose(jvp(head))`` give
    ``embed`` and ``head``)."""
    out = set()
    for part in op_name.split("/"):
        m = WRAPPER.fullmatch(part)
        while m:
            part = m.group(1)
            m = WRAPPER.fullmatch(part)
        out.add(part)
    return out


def program_scopes(session) -> Optional[Tuple[Dict[str, str], Set[str]]]:
    """The scope map and the collectives of every step program the session
    has compiled, or None when it keeps none or none of its ops carries a
    scope."""
    programs = getattr(session, "programs", None)
    if not programs:
        return None
    scopes: Dict[str, str] = {}
    collectives: Set[str] = set()
    for compiled in programs.values():
        text = compiled.as_text()
        scopes.update(scope_map(text))
        collectives |= collective_names(text)
    if not any("forward" in scope_names(n) for n in scopes.values()):
        return None
    return scopes, collectives


def split(trace, plane: str, scopes: Dict[str, str],
          collectives: Set[str] = frozenset()) -> Dict[str, int]:
    """Self time (ns) of the ops inside the window on ``plane``, summed
    by part (``PARTS``), with ``busy`` (all of it), ``unscoped``, and
    ``grad_sync_collectives`` (the ``collectives`` under ``grad_sync``)."""
    lo, hi = tr.window(trace)
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.devices[plane]
              if e > lo and s < hi]
    out = dict.fromkeys(list(PARTS) + ["busy", "unscoped",
                                       "grad_sync_collectives"], 0)
    for name, t in tr.self_times(inside):
        out["busy"] += t
        if name.startswith(tr.KERNEL_PREFIX):
            name = name[len(tr.KERNEL_PREFIX):]
        op = scopes.get(name)
        if op is None:
            out["unscoped"] += t
            continue
        names = scope_names(op)
        for part, test in PARTS.items():
            if test(op, names):
                out[part] += t
        if "grad_sync" in names and name in collectives:
            out["grad_sync_collectives"] += t
    return out


_LAST: list = [None, None]       # (trace, its split): each trace split once


def per_step_ms(ctx, part: str) -> Optional[float]:
    """A part's device self time per traced step, in ms, on the busiest
    device; None where the program gives no scopes."""
    if _LAST[0] is not ctx.trace:
        found = program_scopes(ctx.session)
        _LAST[:] = [ctx.trace, None if found is None else split(
            ctx.trace, tr.busiest(ctx.trace), *found)]
    got = _LAST[1]
    if got is None or not ctx.steps:
        return None
    return got[part] / ctx.steps / 1e6
