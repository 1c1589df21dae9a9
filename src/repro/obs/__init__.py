"""repro.obs -- unified observability: in-scan metric taps, host-side
span tracing, and one versioned telemetry record.

Three layers (docs/ARCHITECTURE.md section 12):

  taps       ``ExperimentSpec.obs = "none" | "basic" | "full"`` rides
             the scan carry as traced lane state (like schedule /
             fault / wire), recording per-round on-device series:
             loss, exchange-stack norms, grad norms, quarantine
             counts, bytes-on-wire, staleness depth.  Observation-only
             and hash-excluded: ``obs="full"`` trajectories are
             bitwise ``obs="none"`` trajectories.
  trace      host spans over run / build / init / round / eval /
             checkpoint and the server's submit / offer / step, on a
             capturing ``jax.profiler`` timeline at every level;
             :class:`SpanTracer` also records them for Chrome
             trace-event JSON (Perfetto-loadable), and ``obs="none"``
             sessions get the non-recording :class:`NullTracer`.
  telemetry  :class:`Telemetry` -- the one versioned record on
             ``RunResult.telemetry`` / ``ServeReport.obs`` folding
             wall clock, fault/wire/serve counters, obs series and
             spans; the legacy ``timings`` dict is derived from it as
             a deprecated alias.  :func:`prometheus_text` renders
             serving counters + latency histogram as Prometheus text
             exposition.

Quickstart::

    spec = ExperimentSpec(dataset="mnist", mode="devertifl",
                          obs="full", rounds=5)
    sess = Session(spec)
    res = sess.run()
    res.telemetry.series["loss"]        # [rounds] on-device series
    sess.tracer.export("trace.json")    # open in ui.perfetto.dev
    print(sess.tracer.summary())

CLI: ``python -m repro.obs --obs full --trace-out trace.json``.
"""
from repro.obs.registry import (OBS, LEVEL_BASIC, LEVEL_FULL,
                                LEVEL_NONE, ObsEntry, ObsPlan,
                                get_obs_plan, obs_names, register_obs)
from repro.obs.taps import (SERIES_KEYS, ObsImpl, make_obs_impl)
from repro.obs.trace import NullTracer, SpanTracer
from repro.obs.telemetry import (TELEMETRY_SCHEMA_VERSION, Telemetry,
                                 metrics_table)
from repro.obs.prom import LATENCY_BUCKETS_S, prometheus_text

__all__ = [
    "OBS", "LEVEL_NONE", "LEVEL_BASIC", "LEVEL_FULL",
    "ObsPlan", "ObsEntry", "get_obs_plan", "obs_names",
    "register_obs",
    "ObsImpl", "make_obs_impl", "SERIES_KEYS",
    "SpanTracer", "NullTracer",
    "Telemetry", "TELEMETRY_SCHEMA_VERSION", "metrics_table",
    "prometheus_text", "LATENCY_BUCKETS_S",
]
