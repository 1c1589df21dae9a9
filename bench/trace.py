"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps, per chip, the device's operations (the ``XLA Ops`` line) and
programs (``XLA Modules``), and the host's events, clipped to the
benchmark's traced window: the host span named ``WINDOW``.  The
reductions below are plain functions of event lists, so the tests
check them on small made-up traces.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns
    end: float          # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    """``ops[chip]`` / ``modules[chip]``: the device's events per chip;
    ``host``: host events (every host line); ``window``: (start, end)
    of the traced window in ns."""
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)


def op_name(text: str) -> str:
    """``vfl_matmul.1`` of an XLA op event named by its instruction text
    (``%vfl_matmul.1 = f32[64,10]{...} custom-call(...)``)."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def _events(line, name=lambda n: n) -> List[Event]:
    return [Event(name(e.name), float(e.start_ns), float(e.end_ns))
            for e in line.events]


def load(directory: str) -> Trace:
    """The newest trace under ``directory``, clipped to its window."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops[int(m.group(1))] = _events(line, op_name)
            elif m and line.name == MODULES_LINE:
                tr.modules[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                tr.host.extend(_events(line))
    spans = [e for e in tr.host if e.name == WINDOW]
    if spans:
        tr.window = (spans[0].start, spans[0].end)
    else:
        every = [e for evs in tr.ops.values() for e in evs]
        tr.window = (min(e.start for e in every),
                     max(e.end for e in every)) if every else (0.0, 0.0)
    lo, hi = tr.window
    tr.ops = {c: clip(evs, lo, hi) for c, evs in tr.ops.items()}
    tr.modules = {c: clip(evs, lo, hi) for c, evs in tr.modules.items()}
    tr.host = clip(tr.host, lo, hi)
    return tr


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def merge(events: List[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint spans."""
    spans: List[List[float]] = []
    for s, e in sorted((ev.start, ev.end) for ev in events):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e)
        else:
            spans.append([s, e])
    return [(s, e) for s, e in spans]


def busy_ns(events: List[Event]) -> float:
    """Time in which at least one of the events runs."""
    return sum(e - s for s, e in merge(events))


def busy_s(trace: Trace) -> Optional[float]:
    """Device-busy seconds in the window, averaged over the chips; None
    where no operation ran."""
    per_chip = [busy_ns(trace.ops[c]) for c in trace.chips
                if trace.ops[c]]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, averaged over the chips."""
    busy = busy_s(trace)
    if busy is None or trace.window_s <= 0:
        return None
    return 1.0 - busy / trace.window_s


def gaps(events: List[Event], lo: float, hi: float):
    """The idle spans between the union of ``events`` in [lo, hi]."""
    out, t = [], lo
    for s, e in merge(events):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def named(events: List[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def total_by_name(events: List[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def within(events: List[Event], outer: List[Event]) -> List[Event]:
    """The events that start inside one of the ``outer`` events."""
    spans = merge(outer)
    out, j = [], 0
    for e in sorted(events, key=lambda ev: ev.start):
        while j < len(spans) and spans[j][1] < e.start:
            j += 1
        if j < len(spans) and spans[j][0] <= e.start <= spans[j][1]:
            out.append(e)
    return out


def host_doing(spans, host: List[Event]) -> List[str]:
    """What the host was doing in each idle span: the shortest host
    event running at the span's midpoint ("host: untraced" where none
    is), by one sweep over both sorted lists."""
    import heapq
    order = sorted(range(len(spans)), key=lambda i: sum(spans[i]))
    events = sorted((h for h in host if h.name != WINDOW),
                    key=lambda h: h.start)
    names = ["host: untraced"] * len(spans)
    active: list = []
    j = 0
    for i in order:
        t = 0.5 * (spans[i][0] + spans[i][1])
        while j < len(events) and events[j].start <= t:
            heapq.heappush(active, (events[j].end, j))
            j += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        if active:
            names[i] = min((events[k] for _, k in active),
                           key=lambda h: h.dur).name
    return names


CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


def breakdown(trace: Trace, top: int = 10, short_ns: float = 1e4) -> dict:
    """The device operations that took most time (control-flow ops that
    hold others, such as a scan's ``while``, left out), and the
    device-idle time by what the host was doing, summed over the chips;
    idle spans under ``short_ns`` are counted together as "between
    ops"."""
    ops = total_by_name([e for c in trace.chips for e in trace.ops[c]
                         if not CONTAINERS.match(e.name)])
    idle: Dict[str, float] = {}
    lo, hi = trace.window
    for c in trace.chips:
        spans = gaps(trace.ops[c], lo, hi)
        long = [g for g in spans if g[1] - g[0] >= short_ns]
        short = sum(g[1] - g[0] for g in spans if g[1] - g[0] < short_ns)
        if short:
            key = f"between ops (< {short_ns * 1e-3:g} us)"
            idle[key] = idle.get(key, 0.0) + short
        for g, name in zip(long, host_doing(long, trace.host)):
            idle[name] = idle.get(name, 0.0) + (g[1] - g[0])

    def rank(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
