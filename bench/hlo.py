"""Read a compiled program's HLO text: which instructions of its loops
gather.

The profiler trace names each executed instruction (``%fusion.73 = ...``)
but not what a fusion computes.  The compiled program's text says it: a
fusion gathers when its computation holds a ``gather``.  The instructions
that count are those of the ``while`` loops' bodies (and of what those
bodies call, but not the insides of fusions), so a prologue outside the
loop is left out.
"""
from __future__ import annotations

import re

HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
CALLED = re.compile(r"\b(calls|body|condition|to_apply|branch_computations|"
                    r"true_computation|false_computation)=\{?([^}\s]*(?:, "
                    r"%[\w.\-]+)*)\}?")


def parse(text: str) -> dict:
    """``{computation: [(instruction, opcode, {edge: [callee, ...]})]}``."""
    comps: dict = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = HEADER.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            current = None
            continue
        m = INSTRUCTION.match(line)
        if m:
            edges = {}
            for edge, names in CALLED.findall(line):
                edges[edge] = [n.strip().lstrip("%") for n in names.split(",")
                               if n.strip()]
            current.append((m.group(1), m.group(2), edges))
    return comps


def loop_gathers(text: str) -> set:
    """Names of the instructions inside ``while`` bodies that gather: a
    ``gather``, or a fusion whose computation holds one."""
    comps = parse(text)
    memo: dict = {}

    def holds_gather(comp: str) -> bool:
        if comp not in memo:
            memo[comp] = False
            memo[comp] = any(op == "gather" or any(
                holds_gather(c) for cs in edges.values() for c in cs)
                for _, op, edges in comps.get(comp, ()))
        return memo[comp]

    bodies = [c for insts in comps.values() for _, op, edges in insts
              if op == "while" for c in edges.get("body", ())]
    seen, out = set(), set()
    while bodies:
        comp = bodies.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, edges in comps.get(comp, ()):
            if op == "gather" or (op == "fusion" and any(
                    holds_gather(c) for c in edges.get("calls", ()))):
                out.add(name)
            elif op != "fusion":
                bodies += [c for cs in edges.values() for c in cs]
    return out
