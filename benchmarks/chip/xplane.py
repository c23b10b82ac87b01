"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

Reading (``load``) keeps, from the planes of the chips (``/device:TPU:n``),
the events of the ``XLA Ops`` line (one per HLO instruction run; a
``while`` loop's event encloses its body's) and of the ``XLA Modules``
line (one per program run), and from the host planes every span whose
name starts with ``bench.``: the benchmark's own annotations, on the same
clock.  An op's name is its HLO instruction without the ``%`` and the
numeric suffix: ``%ssd_scan.18 = bf16[...] custom-call(...)`` is
``ssd_scan``; a module's is its jitted function: ``jit_train_step(...)``
is ``jit_train_step``.

Reducing (``reduce``) is plain arithmetic over those lists, cut to the
``bench.window`` span:

* busy: the union of the op intervals, averaged over the chips;
* per op name: count, total and self time (minus the ops nested in it);
* per module name: count and total time;
* each idle gap (the window less the union) is put down to the host span
  that overlaps it most, ``bench.window`` itself not counted; a gap that
  no span covers for at least half its length is "no span".
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
NO_SPAN = "no span"
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")
_MODULE = re.compile(r"^([^(]+)")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)      # chip -> [(name, t0, t1)]
    modules: dict = field(default_factory=dict)  # chip -> [(name, t0, t1)]
    spans: list = field(default_factory=list)    # [(name, t0, t1)] host


def op_name(event_name: str) -> str:
    m = _OP.match(event_name.strip())
    return m.group(1) if m else event_name.split(" ", 1)[0]


def module_name(event_name: str) -> str:
    return _MODULE.match(event_name).group(1)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    names: dict = {}

    def short(name):   # one parse per distinct instruction
        got = names.get(name)
        if got is None:
            got = names[name] = op_name(name)
        return got

    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                tr.ops[plane.name] = [
                    (short(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif device and line.name == "XLA Modules":
                tr.modules[plane.name] = [
                    (module_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events]
            elif not device:
                tr.spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("bench."))
    return tr


@dataclass
class Reduced:
    window_s: float
    busy_s: float                 # averaged over the chips
    chips: int
    ops: dict                     # name -> [count, total_s, self_s]
    modules: dict                 # name -> [count, total_s]
    idle_by_span: dict            # span name -> seconds (summed over chips)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][2])[:n]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v[2]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def _union(intervals):
    out = []
    for a, b in sorted((a, b) for _, a, b in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events):
    """Self time of each event on one line: its duration less that of the
    events nested directly inside it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    selft = [e[2] - e[1] for e in evs]
    stack: list[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            selft[stack[-1]] -= b - a
        stack.append(i)
    return evs, selft


class _Spans:
    """Which host span overlaps an interval most, for intervals asked in
    increasing order (the innermost, latest-starting, on a tie)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.i = 0
        self.active: list = []

    def best(self, a, b):
        while self.i < len(self.spans) and self.spans[self.i][1] < b:
            self.active.append(self.spans[self.i])
            self.i += 1
        self.active = [s for s in self.active if s[2] > a]
        best, best_ov = NO_SPAN, (b - a) / 2
        for name, s0, s1 in self.active:
            ov = min(b, s1) - max(a, s0)
            if ov >= best_ov:
                best, best_ov = name, ov
        return best


def reduce(tr: Trace) -> Reduced:
    windows = [(a, b) for n, a, b in tr.spans if n == WINDOW]
    if not windows:
        raise ValueError("the trace has no bench.window span")
    lo, hi = windows[-1]
    chips = sorted(tr.ops)
    if not chips:
        raise ValueError("the trace has no device ops")
    spans = [s for s in tr.spans if s[0] != WINDOW]
    ops: dict = defaultdict(lambda: [0, 0.0, 0.0])
    modules: dict = defaultdict(lambda: [0, 0.0])
    idle: dict = defaultdict(float)
    busy = 0.0
    for chip in chips:
        events = _clip(tr.ops[chip], lo, hi)
        evs, selft = _self_times(events)
        for (name, a, b), s in zip(evs, selft):
            rec = ops[name]
            rec[0] += 1
            rec[1] += (b - a) / 1e9
            rec[2] += s / 1e9
        merged = _union(events)
        busy += sum(b - a for a, b in merged) / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        host = _Spans(spans)
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[host.best(a, b)] += (b - a) / 1e9
        for name, a, b in _clip(tr.modules.get(chip, []), lo, hi):
            modules[name][0] += 1
            modules[name][1] += (b - a) / 1e9
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / len(chips),
                   chips=len(chips), ops=dict(ops), modules=dict(modules),
                   idle_by_span=dict(idle))
