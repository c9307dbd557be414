"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

A TPU plane's "XLA Ops" line holds one event per executed HLO instruction,
named by the instruction's text (``%fusion.73 = f32[...] fusion(...)``).
A ``while`` event spans its whole loop, so only leaf events (those that
contain no other event) count.  Busy time is the union of the leaf
intervals inside the harness's ``window`` span; idle is the rest of the
window.  Collective time is the union of the intervals of all-reduce,
all-gather, all-to-all, collective-permute and reduce-scatter instructions
(their async ``-start``/``-done`` halves included).  Each idle gap of the
first device is named by the harness span (host clock, same trace) that
covers most of it.  The seconds of each instruction, by its name
(``fusion.73``), are kept for the readers that pick instructions out by
what the compiled program says they do (``hlo.py``).
"""
from __future__ import annotations

import re

COLLECTIVE = re.compile(r"^(all-reduce|all-gather|all-to-all|"
                        r"collective-permute|reduce-scatter)(-start|-done)?$")
#: ``%name = <shape> opcode(operands), kind=...``
HLO_TEXT = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9-]*)\((.*?kind=(\w+))?")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
HOST_SPANS = ("solve_call", "host_check")
TOP = 10


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(iv: list) -> float:
    return float(sum(e - s for s, e in iv))


def op_name(text: str) -> tuple[str, str]:
    """``(short name, opcode)`` of an HLO instruction's text; the short
    name is ``name = opcode`` (with the fusion kind, if any)."""
    m = HLO_TEXT.match(text)
    if not m:
        return text, text
    name, opcode, _, kind = m.groups()
    return f"{name} = {opcode}" + (f" {kind}" if kind else ""), opcode


def _leaves(events: list) -> list:
    """The events that contain no other event."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(ev)
    stack: list = []
    for i, (_, s, e) in enumerate(ev):
        while stack and ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(ev, parent) if not p]


def _clip(events: list, w0: float, w1: float) -> list:
    return [(n, max(s, w0), min(e, w1)) for n, s, e in events
            if e > w0 and s < w1]


def read_events(path: str) -> tuple[list, dict]:
    """``(host, devices)``: the harness's host spans as ``(name, start,
    end)`` in ns, and each TPU's operations the same way, keyed by id."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: list = []
    devices: dict = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name == WINDOW or e.name in HOST_SPANS]
    return host, devices


def reduce_events(host: list, devices: dict) -> dict:
    """Busy, collective and window seconds and each instruction's seconds
    (averaged over the devices), the operations that took most time and
    the longest idle gaps."""
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows or not devices:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW}' spans and "
                         f"{len(devices)} TPU planes")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host if n != WINDOW]
    busy, coll, ops, by_instr = [], [], {}, {}
    first = None
    for dev in sorted(devices):
        ev = [(op_name(n), s, e)
              for n, s, e in _clip(_leaves(devices[dev]), w0, w1)]
        u = _union([(s, e) for _, s, e in ev])
        busy.append(_length(u))
        coll.append(_length(_union([(s, e) for (_, code), s, e in ev
                                    if COLLECTIVE.match(code)])))
        for (n, _), s, e in ev:
            ops[n] = ops.get(n, 0.0) + (e - s)
            instr = n.split(" = ")[0]
            by_instr[instr] = by_instr.get(instr, 0.0) + (e - s)
        if first is None:
            first = u
    nd = len(devices)
    gaps = []
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            best, cover = "no harness span", 0.0
            for n, hs, he in spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = n, c
            gaps.append((best, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / nd * 1e-9,
        "collective_s": sum(coll) / nd * 1e-9,
        "devices": nd,
        "device_ops": [[n, t / nd * 1e-9] for n, t in top],
        "instruction_s": {n: t / nd * 1e-9 for n, t in by_instr.items()},
        "idle_gaps": [[n, t] for n, t in gaps[:TOP]],
    }


def reduce_trace(path: str) -> dict:
    return reduce_events(*read_events(path))
