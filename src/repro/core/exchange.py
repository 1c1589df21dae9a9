"""HiddenOutputExchange (Algorithm 2) -- the paper's knowledge-exchange
novelty: during the forward pass, every participant broadcasts its
hidden-layer outputs and each participant SUMS the received tensors with
its own.

Two implementations with identical semantics:

  * hidden_output_exchange: the literal simulation used by the MLP
    reproduction -- per-client hidden outputs are stacked on a leading
    client axis and summed; other clients' contributions are
    stop-gradient'ed, because in the real deployment a client receives
    peers' activations as data and the backward pass is local
    (Algorithm 1 line 12 updates only theta_i).

  * the SPMD form for production models lives in
    repro.models.transformer.exchange_features (psum over the client
    mesh axis inside shard_map); tests assert the two agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# identity outside an audit trace; marks the declared cross-client
# channels / maskable terms for the static auditor (repro.analysis)
from repro.analysis.barrier import tag


@jax.named_scope("exchange")
def hidden_output_exchange(h_all, differentiable=False, client_mask=None):
    """h_all: [n_clients, B, H] per-client hidden outputs.

    Returns [n_clients, B, H]: for client i, h_i + sum of peers' hiddens.
    With differentiable=False (De-VertiFL), peers' terms carry no
    gradient; with True, gradients flow to every contributor (this is
    the VertiComb-style backward exchange used as a baseline).

    client_mask ([n_clients], 1.0 = live) excludes dead padding slots
    from the broadcast sum: a dead client contributes an exact +0.0
    term, so the live clients' exchanged sum is bit-for-bit the
    unpadded sum (adding trailing zeros to an XLA reduction preserves
    every bit -- pinned in tests/test_padded_engine.py).  Dead rows of
    the *output* are garbage; the protocol masks them out of every
    loss/metric downstream.
    """
    hm = h_all if client_mask is None else \
        tag(h_all * client_mask[:, None, None], "term", "exchange",
            client_axis=0)
    total = tag(hm.sum(axis=0, keepdims=True),      # [1, B, H]
                "declass", "exchange")
    if differentiable:
        return jnp.broadcast_to(total, h_all.shape)
    peers = jax.lax.stop_gradient(total - hm)       # const contribution
    return h_all + peers


@jax.named_scope("exchange")
def scheduled_exchange(h_all, h_ref, eff_mask):
    """Exchange where the broadcast tensors come from a schedule's
    reference stack (repro.schedule): client i consumes its OWN
    current ``h_all[i]`` plus the eff_mask-weighted sum of ``h_ref``
    excluding its own reference contribution.  ``h_ref`` is data (a
    stop-gradient current stack, a stale ring slot, or a
    double-buffer front), so gradients flow only through ``h_all`` --
    devertifl semantics by construction.

    ``eff_mask`` composes liveness with per-round participation: a
    dropped client's reference term is an exact +0.0 in the sum (it
    sends nothing) while its own row still receives the participants'
    total (it missed the round; the round did not miss it).

    With ``h_ref == stop_gradient(h_all)`` and an all-live eff_mask
    this is the same reduction order as ``hidden_output_exchange(...,
    differentiable=False)`` -- bit-for-bit, which is how the
    degenerate schedules (stale_k:0, partial:1.0) reduce to sync
    (tests/test_schedule.py)."""
    hm = tag(h_ref * eff_mask[:, None, None], "term", "exchange",
             client_axis=0)
    total = tag(hm.sum(axis=0, keepdims=True),      # [1, B, H]
                "declass", "exchange")
    return h_all + (total - hm)


def screen_exchange(payload, last_good, max_abs):
    """Non-finite/magnitude screen over a per-client exchange stack.

    ``payload`` is [n_clients, B, H] about to enter the exchange sum;
    a client's slice is BAD when it contains any non-finite value or
    its magnitude exceeds ``max_abs`` (a NaN maximum compares False
    against the threshold, so both tests catch it independently).  Bad
    slices are replaced with that client's ``last_good`` slice (zeros
    before its first clean round -- the exchange-free cold-start
    idiom), which keeps NaN/Inf out of the reduction entirely: masking
    AFTER the sum would still poison it, since NaN * 0.0 is NaN.

    Returns ``(screened, bad)`` with ``bad`` a [n_clients] bool mask of
    quarantined slots.  The caller (repro.faults.FaultImpl) drops
    quarantined clients from the round's FedAvg weighting exactly like
    dead padded slots and counts the events into telemetry.  Every op
    here (is_finite / reduce_and / reduce_max / select_n) is handled
    by the static auditor's taint and deadness interpreters, and
    ``bad[i]`` derives only from client i's payload, so the per-slot
    separation contract is preserved."""
    red = tuple(range(1, payload.ndim))
    ok = jnp.isfinite(payload).all(axis=red) & \
        (jnp.abs(payload).max(axis=red) <= jnp.float32(max_abs))
    bad = ~ok
    sel = bad.reshape((-1,) + (1,) * (payload.ndim - 1))
    return jnp.where(sel, last_good, payload), bad


@jax.named_scope("exchange")
def select_cached_exchange(h_fresh, h_cached, use_cached):
    """Serving-path cache splice (repro.serving.federated): per-slot
    SELECT between a freshly computed exchange-point stack and one
    served from the hot-entity cache.

    ``h_fresh``/``h_cached`` are [n_clients, S, W] slot stacks;
    ``use_cached`` is a [S] 0/1 gate (client_mask-style: a traced
    runtime value, never a python branch, so the slot count and cache
    state can vary per step without retracing).  ``jnp.where`` is an
    exact element select -- a slot with gate 0 gets ``h_fresh``'s bits
    untouched and a slot with gate 1 gets the cached bits untouched --
    which is the whole bitwise-parity story for the serving cache: a
    cached stack was itself captured from this select's output on an
    earlier step, and everything downstream (exchange sum, rest-of-
    network, argmax) is per-row, so cache on/off cannot change a
    single bit of any request's prediction."""
    sel = use_cached[None, :, None] != 0
    return jnp.where(sel, h_cached, h_fresh)


def fedavg(stacked_params, client_mask=None):
    """P2P weight exchange + FedAvg (Algorithm 1 lines 16-19): every
    client receives every peer's weights and averages. stacked_params
    has a leading client axis on every leaf; returns the same structure
    with every client's slot set to the mean.

    client_mask weights the average so dead padding slots contribute
    nothing (live mean is broadcast to every slot, dead ones included,
    keeping the all-clients-synced invariant).  The masked mean is
    computed as ``sum * (1/n_live)`` -- a multiply, exactly how XLA
    lowers ``mean`` -- so the unpadded all-ones mask reproduces
    ``leaf.mean(axis=0)`` bit for bit."""
    if client_mask is None:
        def avg(leaf):
            m = tag(leaf.mean(axis=0, keepdims=True),
                    "declass", "fedavg")
            return jnp.broadcast_to(m, leaf.shape)
    else:
        inv_live = 1.0 / client_mask.sum()

        def avg(leaf):
            cm = client_mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
            term = tag(leaf * cm, "term", "fedavg", client_axis=0)
            m = tag(term.sum(axis=0, keepdims=True) * inv_live,
                    "declass", "fedavg")
            return jnp.broadcast_to(m, leaf.shape)
    return jax.tree.map(avg, stacked_params)
