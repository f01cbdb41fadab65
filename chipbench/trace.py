"""From a profiler trace to device time: busy, idle, per program, per op.

Events are ``(name, start_s, duration_s)`` on one clock.  The device's
busy time is the union of its op intervals inside the window; the idle
share is one minus busy over the window; each idle gap is named after
the innermost host span that covers its middle, which is what the host
was doing while the device waited.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]

WINDOW_SPAN = "chipbench.window"
ANSWER_SPAN = "chipbench.answer"


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals into disjoint ones, in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) around disjoint busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(text: str) -> str:
    """An HLO op's instruction name from its trace text
    (``%fusion.3 = f32[5] fusion(...)`` -> ``fusion.3``), or a program's
    name without its fingerprint (``jit_f(123)`` -> ``jit_f``)."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return head.split("(", 1)[0]


def self_times(events: Sequence[Event]) -> List[Event]:
    """Each event's duration less that of the events nested directly in
    it (a loop op encloses the ops of its body on the same line)."""
    out: List[List] = []
    stack: List[List] = []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1] + stack[-1][3]:
            stack.pop()
        item = [name, s, d, d]          # name, start, self, duration
        if stack:
            stack[-1][2] -= d
        stack.append(item)
        out.append(item)
    return [(n, s, max(selfd, 0.0)) for n, s, selfd, _ in out]


def host_label(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """Name of the shortest host span covering the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    best, best_len = "no host span", float("inf")
    for name, s, d in host:
        if s <= mid <= s + d and d < best_len and name != WINDOW_SPAN:
            best, best_len = name, d
    return best


@dataclass
class Summary:
    """One traced window, reduced."""

    window_s: float
    busy_s: float
    op_s: Dict[str, float] = field(default_factory=dict)
    program_s: Dict[str, float] = field(default_factory=dict)
    idle: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def program_seconds(self, name: str) -> float:
        """Device seconds of the programs of one jitted function."""
        return sum(s for n, s in self.program_s.items()
                   if n in (f"jit_{name}", name))

    def op_seconds(self, name: str) -> float:
        """Self seconds of the ops named ``name`` or ``name.<n>``."""
        return sum(s for n, s in self.op_s.items()
                   if n == name or n.startswith(name + "."))

    def breakdown(self, top: int = 10) -> Dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle[:top]]}


def reduce(devices: Sequence[Dict[str, List[Event]]], host: Sequence[Event],
           window: Tuple[float, float]) -> Summary:
    """``devices``: per chip, its ``ops`` and ``programs`` events.  Busy
    time is averaged over the chips; idle gaps are those of the first."""
    lo, hi = window
    op_s: Dict[str, float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    busy_total, idle = 0.0, []
    for i, dev in enumerate(devices):
        ivs = [(s, s + d) for _, s, d in dev["ops"]]
        for name, s, d in self_times(dev["ops"]):
            if s + d > lo and s < hi:
                op_s[op_name(name)] += min(s + d, hi) - max(s, lo)
        for name, s, d in dev["programs"]:
            if s + d > lo and s < hi:
                program_s[op_name(name)] += min(s + d, hi) - max(s, lo)
        busy = clip(union(ivs), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            idle = sorted(((host_label(g, host), g[1] - g[0])
                           for g in gaps(busy, lo, hi)), key=lambda x: -x[1])
    return Summary(window_s=hi - lo, busy_s=busy_total / max(len(devices), 1),
                   op_s=dict(op_s), program_s=dict(program_s), idle=idle)


def read_xplane(directory: str, n_chips: int) -> Summary:
    """Reduce the profiler's trace written under ``directory``: the first
    ``n_chips`` TPU planes' "XLA Ops" and "XLA Modules" lines, the host's
    spans, and the window its ``chipbench.window`` span marks."""
    import jax
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no trace was written under {directory}")
    newest = max(paths, key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(newest)
    devices, host = [], []
    for plane in data.planes:
        lines = {ln.name: [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in ln.events] for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            devices.append((plane.name, {
                "ops": lines["XLA Ops"],
                "programs": lines.get("XLA Modules", [])}))
        elif plane.name.startswith("/host:"):
            for events in lines.values():
                host.extend(events)
    devices = [d for _, d in sorted(devices)][:n_chips]
    if not devices:
        raise RuntimeError("the trace holds no TPU plane with XLA ops")
    spans = [(s, s + d) for n, s, d in host if n == WINDOW_SPAN]
    if not spans:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    return reduce(devices, host, spans[0])
