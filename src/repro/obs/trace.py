"""Host-side span tracing: where the wall-clock time of a run or a
serving session actually went.

Every ``with tracer.span("round", cat="train", round=r): ...`` reaches
the ``jax.profiler`` timeline whenever a profiler is capturing, at any
``obs`` level: the span opens a ``jax.profiler.TraceAnnotation`` named
``devertifl.<name>`` on the host, on the same clock as the device's
operations, so a device trace names the host's layers (run, init,
round, eval, submit, step, ...) in its idle gaps.  When nothing
captures, a span costs one ``TraceAnnotation.is_enabled()`` check, and
its arguments are never formatted.

:class:`NullTracer` (``obs="none"``) does only that.  :class:`SpanTracer`
(``obs != "none"``) also keeps an in-memory record of every closed
span with microsecond ``perf_counter`` timestamps from its creation:

  export(path)   Chrome trace-event JSON (the ``{"traceEvents":
                 [...]}`` container of "X" complete events) --
                 loadable in Perfetto / chrome://tracing.
  summary()      a human-readable per-span-name aggregate table
                 (count, total ms, mean ms, share of traced wall).
  to_records()   the raw span dicts, JSON-safe -- what the unified
                 Telemetry record embeds.

Both are strictly HOST-side instruments -- they never touch traced
values, so tracing cannot perturb trajectories (docs/ARCHITECTURE.md
section 12).

``profile_to(dir)`` optionally brackets a region with
``jax.profiler.start_trace/stop_trace`` so a device-level profile is
captured with the spans in it; a profiler that cannot start raises
rather than leaving a run without the trace it asked for.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from contextlib import contextmanager
from typing import List, Optional

from jax.profiler import TraceAnnotation

# prefix of every span's name on the profiler's timeline
PREFIX = "devertifl."
_capturing = TraceAnnotation.is_enabled


class NullTracer:
    """The ``obs="none"`` tracer: records nothing.  A span reaches the
    profiler when one is capturing and is one shared nullcontext
    otherwise, so an instrumented call site costs a method call and
    one ``is_enabled`` check when tracing is off."""

    active = False
    _null = contextlib.nullcontext()

    def span(self, name: str, cat: str = "run", **args):
        """The profiler annotation ``devertifl.<name>`` around the
        with-body while a profiler captures; ``args`` are recorded by
        :class:`SpanTracer` only."""
        if _capturing():
            return TraceAnnotation(PREFIX + name)
        return self._null

    @contextmanager
    def profile_to(self, profile_dir: Optional[str]):
        """A span that additionally captures a ``jax.profiler`` device
        trace into ``profile_dir``.  ``None`` is a pure no-op (no span
        either -- the caller asked for nothing); a profiler that cannot
        start raises."""
        if not profile_dir:
            yield
            return
        import jax
        jax.profiler.start_trace(profile_dir)
        try:
            with self.span("jax_profile", cat="profiler",
                           dir=profile_dir):
                yield
        finally:
            jax.profiler.stop_trace()

    def to_records(self) -> List[dict]:
        return []

    def export(self, path: str):
        raise ValueError(
            "tracing is off (obs='none' builds a NullTracer); build "
            "the session with spec.obs='basic' or 'full' to record "
            "spans")

    def summary(self) -> str:
        return "tracing off (obs='none')"


class SpanTracer(NullTracer):
    """The recording tracer: nested wall-clock spans, kept in memory
    for Chrome trace-event export, that reach the profiler too."""

    active = True

    def __init__(self):
        self.records: List[dict] = []   # closed spans
        self._depth = 0
        self._t0 = time.perf_counter()
        self._pid = os.getpid()

    # ------------------------------------------------------------------
    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, cat: str = "run", **args):
        """Record one nested span around the with-body."""
        depth = self._depth
        self._depth += 1
        mark = NullTracer.span(self, name)
        t_in = time.perf_counter()
        try:
            with mark:
                yield
        finally:
            t_out = time.perf_counter()
            self._depth = depth
            self.records.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": self._us(t_in),
                "dur": (t_out - t_in) * 1e6,
                "depth": depth, "args": args})

    # ------------------------------------------------------------------
    def to_records(self) -> List[dict]:
        """The raw span dicts (JSON-safe; args stringified)."""
        return [{**r, "args": {k: _safe(v)
                               for k, v in r["args"].items()}}
                for r in self.records]

    def export(self, path: str) -> str:
        """Write Chrome trace-event JSON (Perfetto-loadable); returns
        ``path``.  Spans map to "X" complete events on one pid/tid so
        the viewer reconstructs the nesting from ts/dur containment."""
        events = [{"name": r["name"], "cat": r["cat"], "ph": r["ph"],
                   "ts": r["ts"], "dur": r["dur"], "pid": self._pid,
                   "tid": 1, "args": r["args"]}
                  for r in self.to_records()]
        blob = {"traceEvents": events, "displayTimeUnit": "ms"}
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(blob, f)
        return path

    def summary(self) -> str:
        """Per-span-name aggregate table over the recorded spans."""
        spans = self.records
        if not spans:
            return "no spans recorded"
        agg = {}
        for r in spans:
            a = agg.setdefault(r["name"], [0, 0.0])
            a[0] += 1
            a[1] += r["dur"]
        # wall = top-level span time only (nested spans double-count)
        wall = sum(r["dur"] for r in spans if r["depth"] == 0) or 1.0
        lines = [f"{'span':<24} {'count':>6} {'total_ms':>10} "
                 f"{'mean_ms':>9} {'share':>6}"]
        for name, (n, tot) in sorted(agg.items(),
                                     key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<24} {n:>6} {tot / 1e3:>10.2f} "
                         f"{tot / n / 1e3:>9.3f} "
                         f"{min(tot / wall, 1.0):>5.0%}")
        return "\n".join(lines)


def _safe(v):
    """JSON-safe arg value (numbers/strings pass, the rest reprs)."""
    return v if isinstance(v, (int, float, str, bool, type(None))) \
        else repr(v)
