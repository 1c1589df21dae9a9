"""The exchange-transform side of the protocol engine: a wrapper impl
that rides the schedule four-hook contract, so every payload crossing
the (simulated) wire passes one encode-decode round trip inside the
scanned round -- no retrace, ``round_traces == 1`` preserved, and the
transform is a vmappable sweep lane axis exactly like staleness depth
and fault rate.

:class:`WireImpl` wraps any resolved schedule or fault impl (literal
sync is handed over as a depth-0
:class:`~repro.schedule.LaneScheduleImpl`) and sits OUTERMOST in the
engine chain -- ``schedule -> fault -> wire`` -- transforming the
CURRENT hidden stack before the inner machinery sees it:

  select(state, h_now):
      h_tx = decode(encode(h_now))        # topk -> int8 -> dp
      h_ref, inner = inner.select(inner_state, h_tx)

so stale rings buffer what was actually SENT, transport corruption
(repro.faults) poisons the encoded payload, and the exchange guard
screens what a receiver would actually decode.  Each client's own
differentiable hidden output in the loss is untouched -- only the
released stack is transformed, which is the whole privacy story.  The
transform output carries the declared ``wire`` channel's declass tag:
the static auditor (repro.analysis) proves hiddens leave a client
only through this release point.

Determinism contracts: dp noise comes from
``fold_in(fold_in(fold_in(round_key, WIRE_TAG), step), i)`` --
per-client, disjoint from the participation and fault tags -- so
transform realizations are bitwise reproducible and padding-invariant.
All plan parameters (keep fraction, quantize flag, noise scale) ride
the carried state as traced scalars; lanes with different transforms
share one trace.  Integer bytes-on-wire counters (raw vs encoded)
accumulate in the carried state and surface through
``wire_telemetry`` into ``RunResult.timings["wire"]``.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.barrier import tag
from repro.wire.codecs import WIRE_TAG, wire_apply, wire_bytes


class WireImpl:
    """Wire transform layered over an inner schedule/fault impl,
    carried as traced scan state.  Per-lane plan scalars select
    behavior inside one trace."""

    def __init__(self, plan, inner, n_clients, batch_size, width):
        self.plan = plan
        self.inner = inner
        self.n_clients = int(n_clients)
        self.batch_size = int(batch_size)
        self.width = int(width)
        # FaultImpl.init_state takes plan=; LaneScheduleImpl's doesn't
        self._inner_takes_plan = "plan" in inspect.signature(
            inner.init_state).parameters

    def init_state(self, sched, plan=None, wire=None):
        wire = self.plan if wire is None else wire
        if wire.custom is not None:
            raise ValueError(
                f"custom transform {wire.spec!r} cannot ride a wire "
                "lane state; it provides its own impl")
        kw = {}
        if plan is not None:
            if not self._inner_takes_plan:
                raise ValueError(
                    "fault plan given but the inner impl is not a "
                    "fault impl")
            kw["plan"] = plan
        return {
            "inner": self.inner.init_state(sched, **kw),
            # traced plan scalars (lane axis; explicit dtypes keep the
            # retrace lint quiet and lane jaxprs identical)
            "topk_on": jnp.asarray(
                1.0 if wire.topk is not None else 0.0, jnp.float32),
            "topk_p": jnp.asarray(wire.topk_p, jnp.float32),
            "int8_on": jnp.asarray(1.0 if wire.int8 else 0.0,
                                   jnp.float32),
            "dp_on": jnp.asarray(1.0 if wire.dp is not None else 0.0,
                                 jnp.float32),
            "dp_sigma": jnp.asarray(wire.dp_sigma, jnp.float32),
            # per-round wire key + in-round step counter (the dp noise
            # stream; replaced every round_start)
            "wkey": jax.random.PRNGKey(0),
            "wstep": jnp.zeros((), jnp.int32),
            # effective sender count for byte accounting
            "live_n": jnp.zeros((), jnp.float32),
            # telemetry (cumulative integer bytes-on-wire; aggregate
            # scalars, excluded from the per-slot contract like the
            # loss stream)
            "raw_bytes": jnp.zeros((), jnp.int32),
            "enc_bytes": jnp.zeros((), jnp.int32),
        }

    def round_start(self, state, lay, key, round_idx):
        # the inner engine sees the untouched round key, so its
        # participation/fault streams are bit-for-bit the wire-free
        # ones
        inner, eff = self.inner.round_start(state["inner"], lay, key,
                                            round_idx)
        state = {**state, "inner": inner,
                 "wkey": jax.random.fold_in(key, WIRE_TAG),
                 "wstep": jnp.zeros((), jnp.int32),
                 "live_n": eff.sum().astype(jnp.float32)}
        return state, eff

    def select(self, state, h_now):
        st = dict(state)
        with jax.named_scope("wire"):
            skey = jax.random.fold_in(st["wkey"], st["wstep"])
            h_tx = wire_apply(h_now, skey,
                              topk_on=st["topk_on"], topk_p=st["topk_p"],
                              int8_on=st["int8_on"], dp_on=st["dp_on"],
                              dp_sigma=st["dp_sigma"])
            # the declared release point: everything downstream of this
            # tag (rings, guards, the exchange sum) consumes wire data,
            # never a raw hidden -- the taint auditor's proof obligation
            h_tx = tag(h_tx, "declass", "wire")
            raw_b, enc_b = wire_bytes(
                st["live_n"], self.batch_size, self.width,
                topk_on=st["topk_on"], topk_p=st["topk_p"],
                int8_on=st["int8_on"])
            st["wstep"] = st["wstep"] + 1
            st["raw_bytes"] = st["raw_bytes"] + raw_b
            st["enc_bytes"] = st["enc_bytes"] + enc_b
        h_ref, st["inner"] = self.inner.select(st["inner"], h_tx)
        return h_ref, st

    def round_end(self, state):
        return {**state, "inner": self.inner.round_end(state["inner"])}

    def fedavg_mask(self, state, eff_mask):
        """Delegate to the inner impl's hook (the fault layer's
        quarantine drop); identity when the inner has none."""
        fam = getattr(self.inner, "fedavg_mask", None)
        return eff_mask if fam is None else fam(state["inner"],
                                                eff_mask)

    def telemetry(self, state):
        """The inner impl's counters (fault events), surfaced through
        the outermost layer so ``timings["fault"]`` is unchanged by
        wrapping; None when the inner has no telemetry hook."""
        tel = getattr(self.inner, "telemetry", None)
        return None if tel is None else tel(state["inner"])

    def wire_telemetry(self, state):
        """Cumulative integer bytes-on-wire from a (possibly
        lane-batched) carried state, as numpy arrays."""
        return {"raw_bytes": np.asarray(state["raw_bytes"]),
                "encoded_bytes": np.asarray(state["enc_bytes"])}


def make_wire_impl(plan, inner, n_clients, batch_size, width):
    """Build the wire layer for a parsed WirePlan over a resolved
    schedule/fault impl.  Custom plans delegate to their registered
    factory."""
    if plan.custom is not None:
        _, make, args = plan.custom
        return make(inner=inner, n_clients=n_clients,
                    batch_size=batch_size, width=width, args=args)
    return WireImpl(plan, inner, n_clients, batch_size, width)
