"""The fault-injection side of the protocol engine: a wrapper impl
that rides the schedule four-hook contract, so injected adversity is
carried as traced scan state -- no retrace, ``round_traces == 1``
preserved, and fault rate is a vmappable sweep lane axis exactly like
staleness depth.

:class:`FaultImpl` wraps any resolved schedule impl (literal sync is
handed over as a depth-0 :class:`~repro.schedule.LaneScheduleImpl`)
and layers, per round:

  crash      fail-stop outages drawn at ``round_start`` from
             per-client fold_in coins; a down client is removed from
             the round's eff_mask (exact-zero exchange + FedAvg terms,
             the dead-padded-slot idiom) and rejoins after ``dur``
             rounds via a carried countdown.
  straggle   drawn clients' consumed hiddens are served ``d`` steps
             late from a ring of their own past stacks (cold start =
             exchange-free zeros).
  corrupt    drawn clients' payloads are poisoned per-step (NaN or a
             magnitude explosion) BEFORE the guard screen -- which is
             the point: the screen must catch them.

After injection every consumed stack passes
:func:`repro.core.exchange.screen_exchange`: non-finite or
over-magnitude slices are replaced with that client's last-good stack
and the client is quarantined out of the round's FedAvg weighting via
the ``fedavg_mask`` hook.  Event counters (crash / straggle /
corruption / quarantine client-rounds) accumulate in the carried
state and surface through ``telemetry``.

Determinism contracts: all coins come from
``fold_in(fold_in(fold_in(round_key, FAULT_TAG), kind), i)`` --
disjoint from the participation tag and per-client, so fault
realizations are bitwise reproducible and padding-invariant.  All
plan parameters (rates, durations, delay, corruption kind) ride the
carried state as traced scalars; lanes with different plans share one
trace.  The two all-dead fallbacks declassify only a scalar
"is anyone left" bit through the declared ``fault`` channel, keeping
the taint auditor's per-slot separation proof intact
(docs/ARCHITECTURE.md section 9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.barrier import tag
from repro.core.exchange import screen_exchange

# fold_in tag deriving the fault key from the round key (disjoint from
# PARTICIPATION_TAG = 0x5EED and the epoch-permutation split)
FAULT_TAG = 0xFA17
_CRASH, _STRAGGLE, _CORRUPT = 1, 2, 3

# exchange-guard magnitude threshold: hidden stacks in every shipped
# config sit orders of magnitude below this, scale-corrupted ones
# orders of magnitude above
GUARD_MAX = 1e6
# the "scale" corruption factor -- finite, but far past GUARD_MAX
CORRUPT_SCALE = 1e9


def _fault_coins(key, kind, n, p):
    """[n] float32 Bernoulli(p) coins, one per client slot, each from
    ``fold_in(fold_in(fold_in(key, FAULT_TAG), kind), i)`` -- per-client
    derivation for padding invariance (the participation_mask idiom)."""
    fkey = jax.random.fold_in(jax.random.fold_in(key, FAULT_TAG), kind)
    return jax.vmap(
        lambda i: jax.random.bernoulli(jax.random.fold_in(fkey, i), p)
    )(jnp.arange(n, dtype=jnp.int32)).astype(jnp.float32)


def _alive_or(masked, fallback):
    """``masked`` unless it kills every client, else ``fallback``.  The
    scalar liveness bit aggregates every slot's fate, so it crosses the
    per-slot taint boundary -- declassified through the declared
    ``fault`` channel (identity outside an audit trace)."""
    pred = tag(masked.sum(), "declass", "fault") > 0
    return jnp.where(pred, masked, fallback)


class FaultImpl:
    """Fault layers over an inner schedule impl, carried as traced
    scan state.  ``max_delay`` (static) sizes the straggler ring;
    per-lane plan scalars select behavior inside one trace."""

    def __init__(self, plan, inner, n_clients, batch_size, width,
                 max_delay=None):
        self.plan = plan
        self.inner = inner
        self.n_clients = int(n_clients)
        self.batch_size = int(batch_size)
        self.width = int(width)
        self.max_delay = max(plan.max_delay, int(max_delay or 0))

    def init_state(self, sched, plan=None):
        plan = self.plan if plan is None else plan
        if plan.max_delay > self.max_delay:
            raise ValueError(f"fault plan {plan.spec!r} needs a "
                             f"straggler ring of {plan.max_delay} "
                             f"slots but this impl holds "
                             f"{self.max_delay}")
        n, b, w = self.n_clients, self.batch_size, self.width
        st = {
            "inner": self.inner.init_state(sched),
            # traced plan scalars (lane axis; explicit dtypes keep the
            # retrace lint quiet and lane jaxprs identical)
            "crash_p": jnp.asarray(plan.crash_p, jnp.float32),
            "crash_dur": jnp.asarray(plan.max_dur, jnp.int32),
            "strag_p": jnp.asarray(plan.straggle_p, jnp.float32),
            "strag_d": jnp.asarray(plan.max_delay, jnp.int32),
            "corrupt_p": jnp.asarray(plan.corrupt_p, jnp.float32),
            "corrupt_nan": jnp.asarray(
                1.0 if plan.corrupt_kind == "nan" else 0.0, jnp.float32),
            # per-client carried fate
            "crash_left": jnp.zeros((n,), jnp.int32),
            "strag_mask": jnp.zeros((n,), jnp.float32),
            "corrupt_mask": jnp.zeros((n,), jnp.float32),
            "quar": jnp.zeros((n,), jnp.float32),
            "live": jnp.zeros((n,), jnp.float32),
            "last_good": jnp.zeros((n, b, w), jnp.float32),
            # telemetry (client-round event counts; aggregate scalars,
            # excluded from the per-slot contract like the loss stream)
            "crash_events": jnp.zeros((), jnp.int32),
            "strag_events": jnp.zeros((), jnp.int32),
            "corrupt_events": jnp.zeros((), jnp.int32),
            "quar_events": jnp.zeros((), jnp.int32),
        }
        if self.max_delay > 0:
            st["ring"] = jnp.zeros((self.max_delay, n, b, w),
                                   jnp.float32)
        return st

    def round_start(self, state, lay, key, round_idx):
        # the inner schedule sees the untouched round key, so its
        # participation stream is bit-for-bit the fault-free one
        inner, eff = self.inner.round_start(state["inner"], lay, key,
                                            round_idx)
        cm = lay.client_mask
        n = self.n_clients
        # crash countdowns: tick down, then draw fresh outages among
        # clients currently up
        left = jnp.maximum(state["crash_left"] - 1, 0)
        up = (left == 0).astype(jnp.float32)
        new_crash = _fault_coins(key, _CRASH, n, state["crash_p"]) * up
        left = jnp.where(new_crash > 0, state["crash_dur"], left)
        down = (left > 0).astype(cm.dtype)
        eff = _alive_or(eff * (1.0 - down), eff)
        strag = _fault_coins(key, _STRAGGLE, n, state["strag_p"]) * cm
        corrupt = _fault_coins(key, _CORRUPT, n, state["corrupt_p"]) * cm
        state = {
            **state, "inner": inner, "crash_left": left,
            "strag_mask": strag, "corrupt_mask": corrupt,
            "quar": jnp.zeros_like(state["quar"]), "live": cm,
            "crash_events": state["crash_events"]
            + (new_crash * cm).sum().astype(jnp.int32),
            "strag_events": state["strag_events"]
            + strag.sum().astype(jnp.int32),
            "corrupt_events": state["corrupt_events"]
            + corrupt.sum().astype(jnp.int32),
        }
        return state, eff

    def select(self, state, h_now):
        h_ref, inner = self.inner.select(state["inner"], h_now)
        with jax.named_scope("guard"):
            return self._guard({**state, "inner": inner}, h_ref, h_now)

    def _guard(self, st, h_ref, h_now):
        """Stragglers' delays, transport corruption and the screen of
        the consumed stack, after the inner layer's select."""
        if self.max_delay > 0:
            # stragglers' consumed stacks are their own, d steps old
            # (ring read before push, the LaneScheduleImpl idiom)
            ring, d = st["ring"], st["strag_d"]
            idx = jnp.clip(self.max_delay - d, 0, self.max_delay - 1)
            old = jax.lax.dynamic_index_in_dim(ring, idx,
                                               keepdims=False)
            sm = st["strag_mask"] * (d > 0)
            h_ref = jnp.where(sm[:, None, None] > 0, old, h_ref)
            st["ring"] = jnp.concatenate([ring[1:], h_now[None]])
        # transport corruption of the consumed payload (pre-screen)
        poison = jnp.where(st["corrupt_nan"] > 0,
                           jnp.full_like(h_ref, jnp.nan),
                           h_ref * jnp.float32(CORRUPT_SCALE))
        h_ref = jnp.where(st["corrupt_mask"][:, None, None] > 0,
                          poison, h_ref)
        # the guard: screen every consumed stack, quarantine bad slots
        h_ref, bad = screen_exchange(h_ref, st["last_good"], GUARD_MAX)
        st["last_good"] = h_ref
        st["quar"] = jnp.maximum(st["quar"],
                                 bad.astype(jnp.float32))
        return h_ref, st

    def round_end(self, state):
        return {**state,
                "inner": self.inner.round_end(state["inner"]),
                "quar_events": state["quar_events"]
                + (state["quar"] * state["live"]).sum()
                .astype(jnp.int32)}

    def fedavg_mask(self, state, eff_mask):
        """Drop this round's quarantined clients from the FedAvg
        weighting -- exact-zero terms, like dead padded slots."""
        return _alive_or(eff_mask * (1.0 - state["quar"]), eff_mask)

    def telemetry(self, state):
        """Cumulative client-round event counts from a (possibly
        lane-batched) carried state, as numpy arrays."""
        return {"crashes": np.asarray(state["crash_events"]),
                "straggles": np.asarray(state["strag_events"]),
                "corruptions": np.asarray(state["corrupt_events"]),
                "quarantined": np.asarray(state["quar_events"])}


def make_fault_impl(plan, inner, n_clients, batch_size, width,
                    max_delay=None):
    """Build the fault layer for a parsed FaultPlan over a resolved
    schedule impl.  ``max_delay`` overrides the straggler ring depth
    (sweeps size it to the largest delay across their lanes).  Custom
    plans delegate to their registered factory."""
    if plan.custom is not None:
        _, make, args = plan.custom
        return make(inner=inner, n_clients=n_clients,
                    batch_size=batch_size, width=width, args=args)
    return FaultImpl(plan, inner, n_clients, batch_size, width,
                     max_delay=max_delay)
