"""The schedule-aware side of the protocol engine: schedule impls
(the scan-carry state machines) and the devertifl step builder that
consumes them.

Every impl implements the four-hook contract the round function
drives (docs/ARCHITECTURE.md section 7):

  init_state(sched) -> pytree
      The schedule's scan-carry slot.  Empty pytrees are legal (the
      sync lane carries ``{}``); buffers are float32 zeros, so the
      first consumed exchanges of a cold start are exact-zero "no
      peers yet" terms.
  round_start(state, lay, key, round_idx) -> (state, eff_mask)
      Called once per round with the ROUND key.  eff_mask is the
      effective participation mask for the round --
      ``lay.client_mask`` composed with the per-round participation
      draw -- and weights both the exchange sum and the FedAvg.
  select(state, h_now) -> (h_ref, state)
      Called once per step with the stop-gradient CURRENT hidden
      stack ``h_now [n, B, W]``.  Returns the reference stack whose
      masked sum peers consume this step (``h_now`` itself for
      synchronous families) and the advanced state (ring push /
      back-slot fill).
  round_end(state) -> state
      Called after the round's scan (double_buffer's front/back swap).

The step built by :func:`make_sched_step_fn` keeps devertifl
semantics: each client's gradient flows only through its OWN current
hidden output; everything consumed from peers -- current, stale, or
absent -- is data.  The masked and slice first-layer families keep
their historical reduction orders, which is what lets ``stale_k:0``
and ``partial:1.0`` reduce bit-for-bit to the sync engine
(tests/test_schedule.py pins this).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.exchange import scheduled_exchange

# fold_in tag deriving the per-round participation key from the round
# key (disjoint from the epoch-permutation split of the same key)
PARTICIPATION_TAG = 0x5EED


def participation_mask(sched_state, lay, key, round_idx):
    """The per-round effective participation mask: ``client_mask``
    composed with a Bernoulli(p) draw from the round key (or a
    deterministic rotating keep-set), guarded so at least one live
    client always participates.  With p == 1.0 every value is
    bit-for-bit ``lay.client_mask`` (x * 1.0 preserves bits; the
    uniform draw is strictly < 1.0)."""
    cm = lay.client_mask
    p, det = sched_state["p"], sched_state["det"]
    n = cm.shape[0]
    # per-client draws from fold_in(pkey, i), NOT one shaped draw:
    # client i's coin must depend only on (round key, i) so a padded
    # client axis leaves the live clients' participation stream
    # bit-for-bit unchanged (a single bernoulli(key, p, (n,)) call
    # changes every draw when n grows)
    pkey = jax.random.fold_in(key, PARTICIPATION_TAG)
    bern = jax.vmap(
        lambda i: jax.random.bernoulli(jax.random.fold_in(pkey, i), p)
    )(jnp.arange(n, dtype=jnp.int32)).astype(cm.dtype)
    n_live = cm.sum().astype(jnp.int32)
    keep = jnp.maximum(1, jnp.round(p * n_live.astype(cm.dtype))
                       .astype(jnp.int32))
    rank = jnp.mod(jnp.arange(n, dtype=jnp.int32)
                   + round_idx.astype(jnp.int32),
                   jnp.maximum(n_live, 1))
    rot = (rank < keep).astype(cm.dtype)
    part = jnp.where(det > 0, rot, bern)
    eff = cm * part
    return jnp.where(eff.sum() > 0, eff, cm)


class LaneScheduleImpl:
    """The sync / stale_k / partial family with the staleness depth
    ``k``, participation ``p``, and the deterministic flag riding the
    carried STATE as traced scalars -- so a sweep can stack lanes with
    different (k, p) values on one vmapped axis and compile the round
    ONCE across schedule values.  ``max_k`` (static) sizes the ring
    buffer; per-lane ``k <= max_k`` selects how far back to read.

    Ring semantics: ``select`` at step t sees ``buf[max_k - j]`` as
    the stack pushed j steps ago, consumes ``buf[max_k - k]`` (k = 0
    consumes ``h_now`` itself), then pushes ``h_now`` at the end."""

    def __init__(self, max_k, n_clients, batch_size, width):
        if max_k < 0:
            raise ValueError(f"max_k must be >= 0, got {max_k}")
        self.max_k = int(max_k)
        self.n_clients = int(n_clients)
        self.batch_size = int(batch_size)
        self.width = int(width)

    def init_state(self, sched):
        if sched.k > self.max_k:
            raise ValueError(f"schedule {sched.spec!r} needs a ring of "
                             f"{sched.k} slots but this impl holds "
                             f"{self.max_k}")
        st = {"k": jnp.asarray(sched.k, jnp.int32),
              "p": jnp.asarray(sched.p, jnp.float32),
              "det": jnp.asarray(float(sched.deterministic),
                                 jnp.float32)}
        if self.max_k > 0:
            st["buf"] = jnp.zeros(
                (self.max_k, self.n_clients, self.batch_size,
                 self.width), jnp.float32)
        return st

    def round_start(self, state, lay, key, round_idx):
        return state, participation_mask(state, lay, key, round_idx)

    def select(self, state, h_now):
        if self.max_k == 0:
            return h_now, state
        buf, k = state["buf"], state["k"]
        idx = jnp.clip(self.max_k - k, 0, self.max_k - 1)
        stale = jax.lax.dynamic_index_in_dim(buf, idx, keepdims=False)
        h_ref = jnp.where(k > 0, stale, h_now)
        return h_ref, {**state,
                       "buf": jnp.concatenate([buf[1:], h_now[None]])}

    def round_end(self, state):
        return state

    @property
    def identity_select(self):
        """True when ``select`` statically returns ``h_now`` itself
        (depth-0 ring): the step builder then skips the second
        forward pass the ring formulation needs (see
        make_sched_step_fn)."""
        return self.max_k == 0


class DoubleBufferImpl:
    """Round-granularity pipelining: every step of round t consumes
    the ``front`` slot -- the hidden stack captured at the end of
    round t-1 (zeros for round 0) -- while each step overwrites
    ``back`` with its current stack; ``round_end`` promotes back to
    front.  This is the two-slot schedule a real deployment would run
    to fully overlap the exchange with a round of local compute."""

    def __init__(self, n_clients, batch_size, width):
        self.n_clients = int(n_clients)
        self.batch_size = int(batch_size)
        self.width = int(width)

    def init_state(self, sched):
        z = jnp.zeros((self.n_clients, self.batch_size, self.width),
                      jnp.float32)
        return {"front": z, "back": z}

    def round_start(self, state, lay, key, round_idx):
        return state, lay.client_mask

    def select(self, state, h_now):
        return state["front"], {**state, "back": h_now}

    def round_end(self, state):
        return {"front": state["back"], "back": state["back"]}


def make_schedule_impl(sched, n_clients, batch_size, width, max_k=None):
    """Build the impl for a parsed Schedule.  ``max_k`` overrides the
    ring depth (sweeps size it to the largest k across their lanes)."""
    if sched.custom is not None:
        _, make, args = sched.custom
        return make(n_clients=n_clients, batch_size=batch_size,
                    width=width, args=args)
    if sched.double_buffer:
        return DoubleBufferImpl(n_clients, batch_size, width)
    return LaneScheduleImpl(sched.k if max_k is None else max_k,
                            n_clients, batch_size, width)


def make_sched_step_fn(model, opt, pcfg, impl, layout=None,
                       first_layer_fn=None):
    """One schedule-aware devertifl optimizer step:

      step(params, opt_state, lay, eff_mask, sstate, xb, yb, step_idx)
        -> (params, opt_state, sstate, loss)

    Per step: compute the current hidden stack ``h_now`` (data), let
    the impl pick the reference stack ``h_ref`` (current / stale /
    front-buffer), then train each client on its OWN differentiable
    hidden output plus the eff_mask-weighted sum of the reference
    stack excluding its own reference contribution.  The reported
    loss stays the mean over LIVE clients (dropped participants keep
    training locally); only the exchange sum and the FedAvg honor
    eff_mask.
    """
    from repro.core import protocol as P
    if pcfg.mode != "devertifl":
        raise ValueError(f"schedules beyond 'sync' require "
                         f"mode='devertifl', got {pcfg.mode!r}")
    fl = P.resolve_first_layer(pcfg)
    through = partial(P.rest, model, pcfg.exchange_at)

    def update(params, opt_state, grads, step_idx):
        with jax.named_scope("optimizer"):
            params, opt_state, _ = jax.vmap(
                lambda g, s, p: opt.update(g, s, p, step_idx))(
                    grads, opt_state, params)
        return params, opt_state

    # fifth (optional) impl hook: obs taps record the loss vector and
    # grads the step already computed; None for every tap-free impl,
    # so non-obs engines are textually unchanged
    tap = getattr(impl, "tap_step", None)

    if fl == "masked":
        hidden = partial(P.client_hidden, model, pcfg.exchange_at)

        def step(params, opt_state, lay, eff_mask, sstate, xb, yb,
                 step_idx):
            xm = xb[None] * lay.masks[:, None, :]
            h_now = jax.lax.stop_gradient(jax.vmap(hidden)(params, xm))
            h_ref, sstate = impl.select(sstate, h_now)
            # same reduction order as the sync masked step: client i
            # consumes h_i + (masked total) - (own reference term)
            h_sum = P._masked_hidden_sum(h_ref, eff_mask)
            own = h_ref * eff_mask[:, None, None]

            def client_loss(p, x_i, own_i):
                h = hidden(p, x_i) + h_sum - own_i
                return P._ce(through(p, h), yb)

            losses, grads = jax.vmap(jax.value_and_grad(client_loss))(
                params, xm, own)
            params, opt_state = update(params, opt_state, grads,
                                       step_idx)
            if tap is not None:
                sstate = tap(sstate, losses, grads, lay)
            return (params, opt_state, sstate,
                    P._masked_mean(losses, lay.client_mask))
    else:
        first = first_layer_fn or P.make_first_layer_fn(model, pcfg,
                                                        layout)
        hidden_from = partial(P.client_hidden_from, model,
                              pcfg.exchange_at)

        def h_all_fn(ps, lay, xb):
            with jax.named_scope("first_layer"):
                h1 = first(ps, xb, lay)
            with jax.named_scope("tower"):
                return jax.vmap(hidden_from)(ps, h1)

        if getattr(impl, "identity_select", False):
            # depth-0 select statically returns h_now, so the
            # reference stack IS the stop-gradient of the forward the
            # loss needs anyway: compute it ONCE inside grad (the
            # legacy sync formulation -- scheduled_exchange with
            # h_ref == stop_gradient(h_all) is bitwise
            # hidden_output_exchange, see repro.core.exchange) and
            # run select afterwards purely for its observers (obs
            # taps).  The ring formulation below pays a second
            # forward pass to materialize h_now before grad.
            def step(params, opt_state, lay, eff_mask, sstate, xb, yb,
                     step_idx):
                def total(ps):
                    h_all = h_all_fn(ps, lay, xb)
                    h_now = jax.lax.stop_gradient(h_all)
                    h = scheduled_exchange(h_all, h_now, eff_mask)
                    with jax.named_scope("tower"):
                        logits = jax.vmap(through)(ps, h)
                    with jax.named_scope("loss"):
                        losses = jax.vmap(P._ce, in_axes=(0, None))(
                            logits, yb)
                        return ((losses * lay.client_mask).sum(),
                                (losses, h_now))

                grads, (losses, h_now) = jax.grad(
                    total, has_aux=True)(params)
                _, sstate = impl.select(sstate, h_now)
                params, opt_state = update(params, opt_state, grads,
                                           step_idx)
                if tap is not None:
                    sstate = tap(sstate, losses, grads, lay)
                return (params, opt_state, sstate,
                        P._masked_mean(losses, lay.client_mask))

            return step

        def step(params, opt_state, lay, eff_mask, sstate, xb, yb,
                 step_idx):
            h_now = jax.lax.stop_gradient(h_all_fn(params, lay, xb))
            h_ref, sstate = impl.select(sstate, h_now)

            def total(ps):
                h = scheduled_exchange(h_all_fn(ps, lay, xb), h_ref,
                                       eff_mask)
                with jax.named_scope("tower"):
                    logits = jax.vmap(through)(ps, h)
                with jax.named_scope("loss"):
                    losses = jax.vmap(P._ce, in_axes=(0, None))(logits,
                                                                yb)
                    return (losses * lay.client_mask).sum(), losses

            grads, losses = jax.grad(total, has_aux=True)(params)
            params, opt_state = update(params, opt_state, grads,
                                       step_idx)
            if tap is not None:
                sstate = tap(sstate, losses, grads, lay)
            return (params, opt_state, sstate,
                    P._masked_mean(losses, lay.client_mask))

    return step
