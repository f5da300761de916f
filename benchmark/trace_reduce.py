"""Profiler trace -> numbers: busy and idle time of the device, time per
device operation, and the longest idle gaps with what the host was
doing in them.

Input is the ``.xplane.pb`` file ``jax.profiler`` writes.  The window
is the host span the harness opens around its measured window
(``bench.window``); everything is clipped to it.  Busy time is the
union of the intervals of device operations (events on the lines that
name operations, e.g. ``XLA Ops``, on the ``/device:`` planes), averaged
over the devices; an operation's time is the sum of its events'
durations inside the window.  Each device's collectives (the exchange
between chips) are kept as intervals of their own
(``collective_intervals``).
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# device lines that carry one event per executed operation; module and
# step lines overlap them and would count the same time twice
_OP_LINES = ("XLA Ops",)
# the HLO collectives: synchronous as themselves, asynchronous as an
# ``<op>-start`` / ``<op>-done`` pair
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce(path: str, window_span: str = WINDOW_SPAN,
           top: int = 10) -> Optional[dict]:
    """Reduce one trace.  Returns None where the trace holds no device
    plane (nothing ran on an accelerator)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    host_lines = []
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append(line)
                for name, s, e in _events(line):
                    if name == window_span and window is None:
                        window = (s, e)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    lo, hi = window
    op_time: Dict[str, float] = collections.defaultdict(float)
    op_count: Dict[str, int] = collections.defaultdict(int)
    busy_per_device = []
    collectives = []
    gaps: List[Tuple[float, float]] = []
    for plane in device_planes:
        lines = [ln for ln in plane.lines if ln.name in _OP_LINES]
        if not lines:
            continue
        intervals = []
        events = [ev for line in lines for ev in _events(line)]
        collectives.append([((s - lo) * 1e-9, (e - lo) * 1e-9) for s, e
                            in collective_intervals(events, lo, hi)])
        for line in lines:
            for name, s, e in _events(line):
                c = _clip(s, e, lo, hi)
                if c is None:
                    continue
                intervals.append(c)
                op_time[name] += (c[1] - c[0]) * 1e-9
                op_count[name] += 1
        merged = _union(intervals)
        busy_per_device.append(sum(e - s for s, e in merged) * 1e-9)
        prev = lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps.append((prev, hi))
    if not busy_per_device:
        return None
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(g, host_lines, window_span), (g[1] - g[0]) * 1e-9]
            for g in gaps[:top]]
    ops = sorted(((short(n), t) for n, t in op_time.items()
                  if not _container(n)), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_per_device) / len(busy_per_device),
        "devices": len(busy_per_device),
        "op_s": dict(op_time),
        "op_count": dict(op_count),
        "collectives": collectives,
        "breakdown": {"device_ops": [[n, t] for n, t in ops[:top]],
                      "idle_gaps": idle},
    }


def covered_s(intervals) -> float:
    """The length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in _union(list(intervals)))


def _collective(name: str):
    """``(collective, "sync" | "start" | "done")`` of an ``XLA Ops``
    event, or None for any other operation."""
    op = opcode(name)
    for c in COLLECTIVES:
        if op == c:
            return c, "sync"
        if op in (c + "-start", c + "-done"):
            return c, op[len(c) + 1:]
    return None


def collective_intervals(events, lo: float, hi: float):
    """One device's collectives as ``(start, end)`` intervals clipped to
    the window ``[lo, hi]``, from its operations' ``(name, start, end)``
    events in any order.  A synchronous collective is its own event.  An
    asynchronous pair spans from the start of its ``-start`` event to
    the end of the ``-done`` event that names it as its operand.  A
    ``-start`` whose ``-done`` the trace does not hold runs to the
    window's end; a ``-done`` whose ``-start`` it does not hold began
    before the window.
    """
    out = []
    pending = {}                         # open -start: instruction -> start
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        kind = _collective(name)
        if kind is None:
            continue
        c, phase = kind
        instr, _, rest = name.partition(" = ")
        if phase == "start":
            pending[instr.strip()] = s
            continue
        if phase == "done":             # its one operand: the -start
            operand = re.search(r"%[\w.\-]+",
                                rest.partition(f"{c}-done(")[2])
            s = pending.pop(operand.group(0), lo) if operand else lo
        iv = _clip(s, e, lo, hi)
        if iv is not None:
            out.append(iv)
    for s in pending.values():
        iv = _clip(s, hi, lo, hi)
        if iv is not None:
            out.append(iv)
    return sorted(out)


def _label(gap, host_lines, window_span: str) -> str:
    """What the host was doing in a device-idle gap: the host span that
    overlaps the gap most, other than the window itself."""
    best, best_overlap = "(no host span)", 0.0
    for line in host_lines:
        for name, s, e in _events(line):
            if name == window_span:
                continue
            c = _clip(s, e, gap[0], gap[1])
            if c is not None and c[1] - c[0] > best_overlap:
                best, best_overlap = f"{line.name}: {name}", c[1] - c[0]
    return best


def kernel_time(red: dict, match) -> Tuple[float, int]:
    """Summed device seconds and event count of the operations whose
    name ``match(name)`` accepts."""
    t, n = 0.0, 0
    for op, s in red["op_s"].items():
        if match(op):
            t += s
            n += red["op_count"][op]
    return t, n


def custom_call(name: str, prefix: str):
    """Operand count of a Mosaic kernel event (``%<prefix>.N = ...
    custom-call(operands), custom_call_target="tpu_custom_call"``), or
    None for any other event."""
    if not name.startswith("%" + prefix) or \
            'custom_call_target="tpu_custom_call"' not in name:
        return None
    args = name.split(" custom-call(", 1)[1].split(
        "), custom_call_target=", 1)[0]
    return args.count("%")


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``%x = <type> <opcode>(...)``)."""
    rest = name.partition(" = ")[2]
    i = 0
    if rest.startswith("("):          # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    return rest[i:].strip().split("(", 1)[0]


def _container(name: str) -> bool:
    """A device op that only holds others (a loop, a branch, a call):
    its time is its children's, so it stays out of the op breakdown."""
    return opcode(name) in ("while", "conditional", "call")


def short(name: str) -> str:
    """An op's name for the breakdown: its HLO instruction and what it
    calls, without the shapes."""
    instr, _, rest = name.partition(" = ")
    out = f"{instr} {opcode(name)}"
    for key in ('custom_call_target="', "calls="):
        if key in rest:
            out += " " + rest.split(key, 1)[1].split(",", 1)[0].strip('"')
    return out
