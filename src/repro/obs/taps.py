"""In-scan metric taps: a wrapper impl that rides the schedule
four-hook contract and records per-round series ON DEVICE, in the
scan carry -- no host sync per step, no retrace, ``round_traces == 1``
preserved, and the obs level is a vmappable sweep lane axis exactly
like staleness depth, fault rate and wire transform.

:class:`ObsImpl` wraps any resolved schedule / fault / wire impl
(literal sync is handed over as a depth-0
:class:`~repro.schedule.LaneScheduleImpl`) and sits OUTERMOST in the
engine chain -- ``schedule -> fault -> wire -> obs`` -- so it observes
exactly what the inner machinery releases:

  select(state, h_now):
      h_ref, inner = inner.select(inner_state, h_now)
      record ||h_ref||_2 per client      # the released stack's norms

plus a fifth, optional hook the step builder drives AFTER the
optimizer update (``make_sched_step_fn``):

  tap_step(state, losses, grads, lay) -> state
      accumulate the masked-mean loss and per-client gradient norms

The taps are strictly read-only: every value they record is one the
round already computed, and nothing they write feeds back into
params, the exchange, or the key streams -- which is why
``obs="full"`` trajectories are BITWISE ``obs="none"`` trajectories
(tests/test_obs.py pins it) and why ``obs`` is excluded from
spec_hash.  Level gates (``tap_on`` for basic+, ``full_on`` for the
per-client series) ride the carried state as traced scalars; lanes
with different levels share one trace, and a "none" lane records
exact zeros.  ``round_end`` folds the round's accumulators -- and the
inner layers' cumulative counters (guard quarantines, encoded bytes,
staleness depth), found by walking the statically-nested ``"inner"``
chain -- into per-round series arrays via
``dynamic_update_index_in_dim``; ``obs_series`` surfaces them as
numpy on the host.  Recorded values cross to the host through the
declared ``obs`` channel tag, so the taint auditor sees the series
egress as a declared declassification, not a leak.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.barrier import tag

# obs_series key -> carried series slot (all [rounds] or [rounds, n])
SERIES_KEYS = ("loss", "exchange_norm", "grad_norm", "quarantined",
               "encoded_bytes", "staleness")


def _find(state, key):
    """Walk the statically-nested impl state (outer dict, then its
    ``"inner"`` chain) for a carried slot.  The nesting is static
    under trace, so this is a Python-time lookup; None when no layer
    carries the slot (e.g. no fault plan -> no quarantine counter)."""
    while isinstance(state, dict):
        if key in state:
            return state[key]
        state = state.get("inner")
    return None


class ObsImpl:
    """Metric taps layered over an inner schedule/fault/wire impl,
    carried as traced scan state.  Per-lane level gates select what is
    recorded inside one trace; ``rounds`` (static) sizes the series."""

    def __init__(self, plan, inner, n_clients, batch_size, width,
                 rounds):
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        self.plan = plan
        self.inner = inner
        self.n_clients = int(n_clients)
        self.batch_size = int(batch_size)
        self.width = int(width)
        self.rounds = int(rounds)
        # compile-time level bound: tap work ABOVE this level is not
        # even traced (a basic-only session never computes stack or
        # grad norms -- multiplying by a zero gate would still pay
        # for them).  Sweeps stacking mixed levels build the impl at
        # the max stacked level, so the traced gates below still
        # select per lane inside the one shared trace.
        self.static_level = int(plan.level)
        # WireImpl.init_state takes plan= and wire=; FaultImpl's takes
        # plan=; LaneScheduleImpl's takes neither
        self._inner_kws = {
            k for k in ("plan", "wire")
            if k in inspect.signature(inner.init_state).parameters}

    def init_state(self, sched, plan=None, wire=None, obs=None):
        obs = self.plan if obs is None else obs
        if obs.custom is not None:
            raise ValueError(
                f"custom obs plan {obs.spec!r} cannot ride an obs "
                "lane state; it provides its own impl")
        if obs.level > self.static_level:
            raise ValueError(
                f"obs level {obs.spec!r} exceeds the level this impl "
                f"was compiled for ({self.plan.spec!r}); build the "
                "impl from the highest stacked level")
        kw = {}
        for name, val in (("plan", plan), ("wire", wire)):
            if val is not None:
                if name not in self._inner_kws:
                    raise ValueError(
                        f"{name}= given but the inner impl's "
                        f"init_state does not take it")
                kw[name] = val
        n, R = self.n_clients, self.rounds
        return {
            "inner": self.inner.init_state(sched, **kw),
            # traced level gates (lane axis; explicit dtypes keep the
            # retrace lint quiet and lane jaxprs identical)
            "tap_on": jnp.asarray(
                1.0 if obs.level >= 1 else 0.0, jnp.float32),
            "full_on": jnp.asarray(
                1.0 if obs.level >= 2 else 0.0, jnp.float32),
            # current round index (round_start stores it; round_end
            # writes the series row)
            "o_round": jnp.zeros((), jnp.int32),
            # per-round accumulators, zeroed every round_start
            # (aggregate scalars, excluded from the per-slot contract
            # like the loss stream)
            "o_loss": jnp.zeros((), jnp.float32),
            "o_steps": jnp.zeros((), jnp.float32),
            "o_exn": jnp.zeros((n,), jnp.float32),
            "o_gn": jnp.zeros((n,), jnp.float32),
            # per-round series (the obs_series payload)
            "s_loss": jnp.zeros((R,), jnp.float32),
            "s_exn": jnp.zeros((R, n), jnp.float32),
            "s_gn": jnp.zeros((R, n), jnp.float32),
            "s_quar": jnp.zeros((R,), jnp.int32),
            "s_bytes": jnp.zeros((R,), jnp.int32),
            "s_stale": jnp.zeros((R,), jnp.int32),
        }

    def round_start(self, state, lay, key, round_idx):
        # the inner engine sees the untouched round key, so its
        # participation/fault/wire streams are bit-for-bit the
        # obs-free ones
        inner, eff = self.inner.round_start(state["inner"], lay, key,
                                            round_idx)
        z = jnp.zeros_like
        state = {**state, "inner": inner,
                 "o_round": round_idx.astype(jnp.int32),
                 "o_loss": z(state["o_loss"]),
                 "o_steps": z(state["o_steps"]),
                 "o_exn": z(state["o_exn"]),
                 "o_gn": z(state["o_gn"])}
        return state, eff

    def select(self, state, h_now):
        st = dict(state)
        h_ref, st["inner"] = self.inner.select(st["inner"], h_now)
        # per-client L2 norm of the RELEASED stack (post-wire,
        # post-schedule): what actually crossed to peers this step.
        # Recording it is a declared declassification -- the norms
        # leave the exchange flow for the host-readable series
        if self.static_level >= 2:
            with jax.named_scope("taps"):
                exn = tag(jnp.sqrt((h_ref * h_ref).sum(axis=(1, 2))),
                          "declass", "obs")
                st["o_exn"] = st["o_exn"] + st["full_on"] * exn
        return h_ref, st

    @jax.named_scope("taps")
    def tap_step(self, state, losses, grads, lay):
        """The fifth (optional) hook: called by the step builder once
        per optimizer step, AFTER the update, with the per-client loss
        vector and gradient pytree the step already computed.  Pure
        recording -- the returned state differs only in accumulators.
        """
        st = dict(state)
        m = lay.client_mask
        loss = (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
        st["o_loss"] = st["o_loss"] + st["tap_on"] * \
            tag(loss, "declass", "obs")
        st["o_steps"] = st["o_steps"] + st["tap_on"]
        if self.static_level >= 2:
            gn2 = sum((g.reshape(g.shape[0], -1) ** 2).sum(axis=1)
                      for g in jax.tree.leaves(grads))
            st["o_gn"] = st["o_gn"] + st["full_on"] * \
                tag(jnp.sqrt(gn2), "declass", "obs")
        return st

    def round_end(self, state):
        st = dict(state)
        # inner FIRST: the fault layer folds this round's quarantine
        # events into its cumulative counter in round_end, and the
        # series row must include them
        st["inner"] = self.inner.round_end(st["inner"])
        r = jnp.clip(st["o_round"], 0, self.rounds - 1)
        steps = jnp.maximum(st["o_steps"], 1.0)
        on = st["tap_on"] > 0

        def put(series, val):
            return jax.lax.dynamic_update_index_in_dim(
                series, val.astype(series.dtype), r, axis=0)

        st["s_loss"] = put(st["s_loss"], st["o_loss"] / steps)
        st["s_exn"] = put(st["s_exn"], st["o_exn"] / steps)
        st["s_gn"] = put(st["s_gn"], st["o_gn"] / steps)
        # inner layers' cumulative counters, read from the statically
        # nested carry: absent layers record zeros
        for skey, ikey in (("s_quar", "quar_events"),
                           ("s_bytes", "enc_bytes")):
            v = _find(st["inner"], ikey)
            v = jnp.zeros((), jnp.int32) if v is None else v
            st[skey] = put(st[skey], jnp.where(on, v, 0))
        k = _find(st["inner"], "k")     # staleness depth (ring lanes)
        k = jnp.zeros((), jnp.int32) if k is None else k
        st["s_stale"] = put(st["s_stale"], jnp.where(on, k, 0))
        return st

    @property
    def identity_select(self):
        """The taps only READ ``h_ref``; whether select is statically
        the identity is the inner engine's property.  When it is
        (depth-0 sync under obs alone), the step builder takes its
        single-forward fast path and still calls select for the
        recorders."""
        return getattr(self.inner, "identity_select", False)

    # ------------------------------------------------------------------
    # pass-through hooks: the obs layer is observation-only, so the
    # inner machinery's aggregation mask and telemetry surface
    # unchanged through the outermost wrapper
    def fedavg_mask(self, state, eff_mask):
        fam = getattr(self.inner, "fedavg_mask", None)
        return eff_mask if fam is None else fam(state["inner"],
                                                eff_mask)

    def telemetry(self, state):
        tel = getattr(self.inner, "telemetry", None)
        return None if tel is None else tel(state["inner"])

    def wire_telemetry(self, state):
        tel = getattr(self.inner, "wire_telemetry", None)
        return None if tel is None else tel(state["inner"])

    # ------------------------------------------------------------------
    def obs_series(self, state):
        """The recorded per-round series from a (possibly
        lane-batched) carried state, as numpy arrays keyed by
        :data:`SERIES_KEYS`."""
        return {"loss": np.asarray(state["s_loss"]),
                "exchange_norm": np.asarray(state["s_exn"]),
                "grad_norm": np.asarray(state["s_gn"]),
                "quarantined": np.asarray(state["s_quar"]),
                "encoded_bytes": np.asarray(state["s_bytes"]),
                "staleness": np.asarray(state["s_stale"])}


def make_obs_impl(plan, inner, n_clients, batch_size, width, rounds):
    """Build the obs layer for a parsed ObsPlan over a resolved
    schedule/fault/wire impl.  Custom plans delegate to their
    registered factory."""
    if plan.custom is not None:
        _, make, args = plan.custom
        return make(inner=inner, n_clients=n_clients,
                    batch_size=batch_size, width=width, rounds=rounds,
                    args=args)
    return ObsImpl(plan, inner, n_clients, batch_size, width, rounds)
