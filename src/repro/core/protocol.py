"""De-VertiFL training protocol (Algorithms 1 + 2), plus the
non-federated baseline and the VertiComb-style backward-exchange
baseline the paper compares against.

All n clients are simulated in one process by stacking per-client
parameters on a leading axis and vmapping; this is numerically
identical to n communicating peers (the exchange and FedAvg are the
only cross-client dataflows, and they are explicit).

Engine layout
-------------
The protocol is factored into pure functions so the whole federation
can be jitted, scanned, and vmapped:

  * make_first_layer_fn  the slice-aware first layer (see below)
  * make_step_fn      one optimizer step for all clients (mode-specific)
  * make_perm_fn      device-side epoch shuffles (jax.random.permutation)
  * make_round_fn     a full round -- epochs x batches as ONE lax.scan
                      with the round-end FedAvg folded in, so a round is
                      a single XLA executable with no host round-trips
  * make_predict_fn   per-client inference with the evaluation exchange

Slice-aware first layer
~~~~~~~~~~~~~~~~~~~~~~~
Every federation trains on the canonical column layout from
``repro.core.partition.canonicalize``: dataset columns are permuted
once at setup so client i owns the contiguous block-aligned feature
slice [offset_i, offset_i + F_i).  The step/round/predict functions
take a ``LayoutArrays(masks, offsets)`` argument (vmappable over a
seed axis, like masks were before), and ``ProtocolConfig.first_layer``
selects how layer 0 is computed:

  masked   the paper-literal reference: materialize the [n, B, F]
           zero-padded batch and run dense full-width matmuls.  Kept
           bit-for-bit as the reference path.
  slice    x[:, off:off+F_i] @ W[off:off+F_i] per client via XLA
           dynamic_slice -- no padding is materialized and the MXU/ALU
           work drops by ~(n-1)/n on layer 0.  Gradients scatter back
           into the client's W-row block; rows outside the slice get
           the same exact-zero gradient the masked path produces.
  pallas   the block-sparse ``vfl_matmul`` Pallas kernel (with its
           custom VJP) multiplying only the client's weight rows --
           the TPU path; on CPU it runs in interpret mode.
  auto     pallas on TPU, slice elsewhere (the default).

masked and slice/pallas differ only in float reduction order, so
loss/F1 trajectories agree to allclose rather than bitwise
(tests/test_slice_engine.py pins this).

Padded client axes
~~~~~~~~~~~~~~~~~~
``ProtocolConfig.max_clients`` pads the client axis with dead slots
(``Layout.pad``): params/opt state/activations ride arrays of length
max_clients while only the first n_clients slots are live.  Every
cross-client dataflow honors ``LayoutArrays.client_mask`` -- the
exchange sums ``h * client_mask``, FedAvg weights by it, and loss
means divide by the LIVE count via a reciprocal multiply -- so dead
slots contribute exact-zero terms and the live clients' trajectories
are bit-for-bit the unpadded run's in all three first-layer lanes
(tests/test_padded_engine.py).  This is what lets repro.core.sweep
stack different client counts on one vmapped lane axis and compile a
dataset x mode grid once.

Exchange schedules
~~~~~~~~~~~~~~~~~~
``ProtocolConfig.schedule`` selects WHICH exchange tensor each client
consumes at each scanned step (the ``repro.schedule`` subsystem):
"sync" (default) keeps the paper-literal code path below untouched;
"stale_k:k", "double_buffer", and "partial:p" thread a schedule-state
slot through the scan carry (ring buffers of stale hidden stacks, the
two-slot round pipeline, per-round participation masks composed with
``client_mask``).  Non-sync schedules are devertifl-mode only; the
scan and python engines drive the same schedule hooks and stay
bit-for-bit.  See docs/ARCHITECTURE.md section 7.

``DeVertiFL.train`` drives make_round_fn under jit (engine="scan", the
default). A per-batch host-dispatched loop is retained as
engine="python" (same jitted step, host-side batch dispatch). Both
engines consume the identical device-generated permutation stream, so
their loss/F1 trajectories match bit-for-bit at a fixed seed
(tests/test_engine.py asserts this). repro.core.sweep vmaps
make_round_fn over a (seed x client-count x schedule) lane axis for
grid experiments and shards the lanes over the device mesh.

See docs/ARCHITECTURE.md for the scan-round key-derivation and
PermPlan contracts.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.barrier import tag
from repro.configs import get_config
from repro.core import partition as PT
from repro.core.exchange import fedavg, hidden_output_exchange
from repro.data import registry as DR
from repro.kernels.vfl_matmul import vfl_matmul
from repro.metrics import accuracy, f1_score
from repro.models.mlp_model import PaperMLP
from repro.optim import adam
from repro.registry import Registry


@dataclass
class ProtocolConfig:
    dataset: str = "mnist"              # mnist | fmnist | titanic | bank
    n_clients: int = 3
    rounds: int = 5
    epochs: int = 5
    batch_size: int = 64
    lr: float = 1e-3
    # Where HiddenOutputExchange happens. Algorithm 1 exchanges the model
    # output (y-hat); the text/Fig. 1 describe hidden-layer sharing. -1
    # means "logits" (Algorithm-1-faithful); k>=1 means after hidden
    # layer k (text-faithful). Both are supported; -1 is the default and
    # matches the pseudo-code.
    exchange_at: int = -1
    mode: str = "devertifl"             # devertifl | non_federated | verticomb
    fedavg: bool = True
    seed: int = 0
    n_samples: Optional[int] = None     # dataset size override (speed)
    engine: str = "scan"                # scan | python (reference loop)
    first_layer: str = "auto"           # auto | pallas | slice | masked
    # Exchange schedule (repro.schedule spec string): which exchange
    # tensor each client consumes at each step.  "sync" is the
    # paper-literal engine path, untouched; "stale_k:2",
    # "double_buffer", "partial:0.8", "stale_k:4+partial:0.5" run the
    # schedule-aware round (devertifl mode only).
    schedule: str = "sync"
    # Fault plan (repro.faults spec string): deterministic adversity
    # injected into the exchange.  "none" is the untouched engine
    # path; "crash:0.2", "straggle:0.5:2", "corrupt:0.05:scale",
    # "crash:0.2+corrupt:0.05" wrap the schedule impl in the
    # fault-aware state machine (devertifl mode only).
    fault: str = "none"
    # Exchange transform (repro.wire spec string): what the exchanged
    # hidden stacks look like on the wire.  "none" is the untouched
    # engine path; "int8", "topk:0.25", "dp:0.1",
    # "topk:0.5+int8+dp:0.1" wrap the engine impl in the wire
    # encode-decode round trip (devertifl mode only).
    transform: str = "none"
    # Observability level (repro.obs spec string): what the engine
    # records about itself.  "none" is the untouched engine path;
    # "basic"/"full" wrap the engine impl in in-scan metric taps
    # (devertifl mode only).  Observation-only: taps never change a
    # trajectory.
    obs: str = "none"
    # Pad the client axis to this length with dead (masked) slots; None
    # means no padding. Live trajectories are bit-for-bit unchanged --
    # padding only buys shape-uniformity across client counts.
    max_clients: Optional[int] = None
    # Explicit unequal per-client feature counts (must sum to the
    # dataset's feature count); None keeps the registry partition
    # strategy.  Skewed splits ride every first-layer lane unchanged
    # (repro.core.partition.skewed_partition).
    partition_sizes: Optional[Tuple[int, ...]] = None

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    @property
    def padded_clients(self) -> int:
        """Static client-axis length (max_clients or n_clients)."""
        return self.max_clients or self.n_clients


# legacy name->arch map, kept importable; the engine resolves arch via
# the dataset registry so registered custom datasets work everywhere
ARCH_FOR = {"mnist": "paper-mlp-mnist", "fmnist": "paper-mlp-fmnist",
            "titanic": "paper-mlp-titanic", "bank": "paper-mlp-bank"}


def arch_for(dataset: str) -> str:
    """Model-config name for a dataset, via the dataset registry."""
    return DR.get_dataset(dataset).arch


# First-layer backend registry: the three built-in lanes plus "auto"
# hold None (they are implemented inline below); a registered custom
# backend holds a factory ``make(model, pcfg, layout) -> first_fn``
# where ``first_fn(params, xb, lay) -> [n_clients, B, H]`` post-ReLU
# layer-0 activations (the make_first_layer_fn contract).
FIRST_LAYERS = Registry("first_layer")
for _name in ("auto", "masked", "slice", "pallas"):
    FIRST_LAYERS.register(_name, None)


def register_first_layer(name, make):
    """Register a custom first-layer backend for ProtocolConfig /
    ExperimentSpec ``first_layer=name``.  Not supported under the
    padded multi-count sweep vmap (same constraint as pallas)."""
    return FIRST_LAYERS.register(name, make)


def auto_first_layer() -> str:
    """What first_layer="auto" means on this backend.  THE single
    definition of the auto rule -- repro.api.ExperimentSpec
    canonicalizes "auto" through it at construction so a spec (and its
    spec_hash) records the lane that actually runs."""
    return "pallas" if jax.default_backend() == "tpu" else "slice"


def resolve_first_layer(pcfg) -> str:
    """Map the first_layer knob to a concrete path for this backend."""
    fl = pcfg.first_layer
    maker = FIRST_LAYERS.get(fl)    # unknown names raise with options
    if fl == "auto":
        fl = auto_first_layer()
    if pcfg.exchange_at == 0 and fl != "masked":
        # exchanging the raw zero-padded input predates layer 0; only
        # the masked formulation expresses it
        if maker is not None:
            raise ValueError(
                f"first_layer {fl!r} cannot express exchange_at=0 "
                "(the exchange predates layer 0); use "
                "first_layer='masked'")
        fl = "masked"
    return fl


def exchange_width(model, exchange_at) -> int:
    """Trailing width of the exchanged tensor -- what a schedule
    buffer must hold per client per batch row: logits (exchange_at ==
    -1), the raw input (0), or the hidden width (after layer k)."""
    if exchange_at == -1:
        return model.n_classes
    if exchange_at == 0:
        return model.in_features
    return model.hidden


def resolve_schedule(pcfg, model, n_train):
    """pcfg.schedule -> (Schedule, impl).  ``impl`` is None for the
    literal "sync" spec: the legacy engine path runs untouched, which
    is what keeps the paper-literal schedule bit-for-bit pinned.
    Non-sync schedules (including the degenerate stale_k:0 /
    partial:1.0, which run the schedule engine and reduce bitwise) are
    devertifl-mode only: the forward HiddenOutputExchange is what is
    being scheduled, and the backward-exchange/non-federated baselines
    have no data-only peer term for a buffer to replace."""
    from repro.schedule import get_schedule, make_schedule_impl
    sched = get_schedule(pcfg.schedule)
    if sched.is_sync:
        return sched, None
    if pcfg.mode != "devertifl":
        raise ValueError(
            f"schedule {sched.spec!r} requires mode='devertifl'; mode "
            f"{pcfg.mode!r} supports schedule='sync' only")
    impl = make_schedule_impl(
        sched, pcfg.padded_clients, min(pcfg.batch_size, n_train),
        exchange_width(model, pcfg.exchange_at))
    return sched, impl


def resolve_engine(pcfg, model, n_train):
    """pcfg.schedule + pcfg.fault + pcfg.transform + pcfg.obs ->
    (Schedule, impl).  With ``fault="none"``, ``transform="none"``
    and ``obs="none"`` this IS :func:`resolve_schedule` -- same
    objects, same (possibly None) impl, so the adversity-free engine
    stays bit-for-bit the pre-fault, pre-wire, pre-obs one and
    literal sync keeps its legacy path.  Non-none plans (devertifl
    only) wrap the schedule impl in the fault state machine, then the
    wire transform, then the metric taps (the chain is schedule ->
    fault -> wire -> obs: wire outermost of the machinery so it
    transforms what the inner layers buffer/screen, obs outermost of
    all so it observes exactly what is released); literal sync is
    first promoted to a depth-0 ring impl (``stale_k:0``, proven
    bitwise-sync by tests/test_schedule.py) so the wrappers have hooks
    to ride."""
    sched, impl = resolve_schedule(pcfg, model, n_train)
    bs = min(pcfg.batch_size, n_train)
    width = exchange_width(model, pcfg.exchange_at)

    def promoted(impl):
        if impl is None:
            from repro.schedule import LaneScheduleImpl
            impl = LaneScheduleImpl(0, pcfg.padded_clients, bs, width)
        return impl

    fault = getattr(pcfg, "fault", "none")
    from repro.faults import get_fault_plan, make_fault_impl
    plan = get_fault_plan(fault)
    if not plan.is_none:
        if pcfg.mode != "devertifl":
            raise ValueError(
                f"fault plan {plan.spec!r} requires mode='devertifl'; "
                f"mode {pcfg.mode!r} supports fault='none' only")
        impl = make_fault_impl(plan, promoted(impl),
                               pcfg.padded_clients, bs, width)
    transform = getattr(pcfg, "transform", "none")
    from repro.wire import get_wire_plan, make_wire_impl
    wire = get_wire_plan(transform)
    if not wire.is_none:
        if pcfg.mode != "devertifl":
            raise ValueError(
                f"transform {wire.spec!r} requires mode='devertifl'; "
                f"mode {pcfg.mode!r} supports transform='none' only")
        impl = make_wire_impl(wire, promoted(impl),
                              pcfg.padded_clients, bs, width)
    obs = getattr(pcfg, "obs", "none")
    from repro.obs import get_obs_plan, make_obs_impl
    op = get_obs_plan(obs)
    if not op.is_none:
        if pcfg.mode != "devertifl":
            raise ValueError(
                f"obs level {op.spec!r} requires mode='devertifl'; "
                f"mode {pcfg.mode!r} supports obs='none' only")
        impl = make_obs_impl(op, promoted(impl), pcfg.padded_clients,
                             bs, width, rounds=pcfg.rounds)
    return sched, impl


# ---------------------------------------------------------------------------
# pure protocol pieces (shared by DeVertiFL and repro.core.sweep)
# ---------------------------------------------------------------------------
def client_hidden(model, exchange_at, p, xm):
    """Forward up to the exchange point (hidden layer k, or logits)."""
    if exchange_at == -1:
        return model.head(p, model.forward_hidden(p, xm))
    return model.forward_hidden(p, xm, upto=exchange_at)


def client_hidden_from(model, exchange_at, p, h1):
    """client_hidden, but starting from the post-ReLU layer-0 output
    (the slice-aware first-layer paths compute layer 0 themselves)."""
    if exchange_at == -1:
        return model.head(p, model.forward_from(p, h1, start=1))
    return model.forward_from(p, h1, start=1, upto=exchange_at)


def rest(model, exchange_at, p, h):
    """Forward from the exchange point to logits."""
    if exchange_at == -1:
        return h
    for i in range(exchange_at, model.n_hidden):
        h = jax.nn.relu(jnp.matmul(h, p[f"layer_{i}"]["kernel"])
                        + p[f"layer_{i}"]["bias"])
    return model.head(p, h)


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


@jax.named_scope("loss")
def _masked_mean(values, client_mask):
    """Mean over live clients: sum(v * mask) * (1/n_live).  The sum is
    a left fold over the static client axis, so a dead tail of exact
    zeros adds nothing and a padded federation reports its unpadded
    run's loss bit for bit (a ``reduce_sum`` may pair its terms
    differently at different lengths)."""
    term = tag(values * client_mask, "term", "loss", client_axis=0)
    total = term[0]
    for i in range(1, term.shape[0]):
        total = total + term[i]
    return total * (1.0 / client_mask.sum())


@jax.named_scope("exchange")
def _masked_hidden_sum(h_all, client_mask):
    """[n, B, H] -> [B, H] exchange sum excluding dead clients (their
    terms are exact +0.0, preserving the unpadded reduction bits)."""
    hm = tag(h_all * client_mask[:, None, None], "term", "exchange",
             client_axis=0)
    return tag(hm.sum(0), "declass", "exchange")


def make_first_layer_fn(model, pcfg, layout, interpret=None):
    """first(params, xb, lay) -> [n_clients, B, H] post-ReLU layer-0
    activations.  xb is the canonical-order [B, F] batch; lay is the
    LayoutArrays view (lay.offsets is traced -- sweeps vmap it); the
    static slice sizes (and, for pallas, static offsets) come from
    ``layout``.

    CAVEAT (pallas): the kernel slices W with *static* offsets, so
    first_pallas closes over ``layout.offsets`` and ignores the
    runtime ``lay.offsets``.  Callers must pass
    LayoutArrays derived from the same canonical Layout (canonical
    offsets are deterministic per (dataset, n_clients), and
    sweep._stacked_federations raises if lanes ever disagreed); a
    scalar-prefetch offset is the ROADMAP item that would lift this."""
    fl = resolve_first_layer(pcfg)
    # the masked reference keeps its whole-forward formulation inline in
    # make_step_fn / make_predict_fn; only the slice-aware paths split
    # the first layer out
    assert fl != "masked", fl
    assert layout is not None, f"first_layer={fl!r} needs a Layout"
    maker = FIRST_LAYERS.get(fl)
    if maker is not None:           # registered custom backend
        return maker(model, pcfg, layout)
    sizes = layout.sizes

    # Dead (padded) clients own an empty feature slice: their layer-0
    # matmul is the empty contraction [B,0]@[0,H] == 0, so h1 is
    # relu(bias) -- computed directly, no degenerate slice/kernel call.
    # The value never matters (client_mask zeroes dead contributions
    # downstream) but keeping the bias term preserves the historical
    # dynamic_slice semantics for zero-feature clients.
    def dead_h1(xb, b_i, h):
        return jax.nn.relu(jnp.broadcast_to(b_i, (xb.shape[0], h)))

    if fl == "slice":
        def first_slice(params, xb, lay):
            w = params["layer_0"]["kernel"]     # [n, F, H]
            b = params["layer_0"]["bias"]       # [n, H]
            outs = []
            for i, f_i in enumerate(sizes):
                if f_i == 0:
                    outs.append(dead_h1(xb, b[i], w.shape[-1]))
                    continue
                x_i = jax.lax.dynamic_slice(
                    xb, (0, lay.offsets[i]), (xb.shape[0], f_i))
                w_i = jax.lax.dynamic_slice(
                    w[i], (lay.offsets[i], 0), (f_i, w.shape[-1]))
                outs.append(jax.nn.relu(x_i @ w_i + b[i]))
            return jnp.stack(outs)
        return first_slice

    # pallas: the kernel needs static offsets; the canonical layout's
    # offsets are deterministic per (dataset, n_clients), so closing
    # over them is safe even in seed-vmapped sweeps.  interpret=None
    # compiles the kernel on a TPU and interprets it elsewhere.
    offsets = layout.offsets

    def first_pallas(params, xb, lay):
        w = params["layer_0"]["kernel"]
        b = params["layer_0"]["bias"]
        outs = []
        for i, (off, f_i) in enumerate(zip(offsets, sizes)):
            if f_i == 0:
                # dead (and degenerate zero-feature) clients never
                # reach the kernel -- so every kernel call here has
                # client_mask[i] == 1 and needs no gate=; the kernel's
                # gate stays for lanes whose liveness is only known at
                # runtime (e.g. a future scalar-prefetch sweep path)
                outs.append(dead_h1(xb, b[i], w.shape[-1]))
                continue
            x_i = jax.lax.slice_in_dim(xb, off, off + f_i, axis=1)
            y = vfl_matmul(x_i, w[i], off, interpret=interpret)
            outs.append(jax.nn.relu(y + b[i]))
        return jnp.stack(outs)
    return first_pallas


def make_step_fn(model, opt, pcfg, layout=None, first_layer_fn=None):
    """One all-clients optimizer step for pcfg.mode.

    Signature: step(params, opt_state, lay, xb, yb, step_idx)
      -> (params, opt_state, mean_loss).  lay is a LayoutArrays
    argument (not a closure) so sweeps can vmap it over per-seed (and
    per-client-count) partitions; xb is in canonical column order.

    Every cross-client reduction honors lay.client_mask: the exchange
    sums only live clients' hiddens (dead terms are exact zeros) and
    the reported loss is the mean over live clients.  With an all-ones
    mask (unpadded layouts) these are bit-for-bit the unmasked ops.

    first_layer_fn overrides the slice/pallas first layer (the padded
    sweep passes a shape-uniform gather-slice variant that reads sizes
    and offsets from lay instead of closing over layout statics).
    """
    fl = resolve_first_layer(pcfg)
    hidden = partial(client_hidden, model, pcfg.exchange_at)
    through = partial(rest, model, pcfg.exchange_at)

    def update(params, opt_state, grads, step_idx):
        with jax.named_scope("optimizer"):
            params, opt_state, _ = jax.vmap(
                lambda g, s, p: opt.update(g, s, p, step_idx))(
                    grads, opt_state, params)
        return params, opt_state

    if fl == "masked":
        # the paper-literal reference: whole-forward from the
        # materialized [n, B, F] zero-padded batch, per-client
        # value_and_grad -- kept exactly as the pre-slice engine
        def devertifl_step(params, opt_state, lay, xb, yb, step_idx):
            xm = xb[None] * lay.masks[:, None, :]   # [n, B, F] zeropad
            h_all = jax.vmap(hidden)(params, xm)
            h_sum = jax.lax.stop_gradient(
                _masked_hidden_sum(h_all, lay.client_mask))  # peers=data

            def client_loss(p, x_i):
                h_i = hidden(p, x_i)
                # value == full exchanged sum; grad flows only through h_i
                h = h_i + h_sum - jax.lax.stop_gradient(h_i)
                return _ce(through(p, h), yb)

            losses, grads = jax.vmap(jax.value_and_grad(client_loss))(
                params, xm)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, _masked_mean(losses, lay.client_mask)

        def nonfed_step(params, opt_state, lay, xb, yb, step_idx):
            xm = xb[None] * lay.masks[:, None, :]

            def client_loss(p, x_i):
                h_i = hidden(p, x_i)
                return _ce(through(p, h_i), yb)

            losses, grads = jax.vmap(jax.value_and_grad(client_loss))(
                params, xm)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, _masked_mean(losses, lay.client_mask)

        def verticomb_step(params, opt_state, lay, xb, yb, step_idx):
            xm = xb[None] * lay.masks[:, None, :]

            def total_loss(ps):
                h_all = jax.vmap(hidden)(ps, xm)
                # grads flow to all LIVE contributors; a dead client's
                # hidden is multiplied by 0, so its params get exact
                # zero grads from peers' losses
                h_sum = _masked_hidden_sum(h_all, lay.client_mask)
                logits = jax.vmap(lambda p: through(p, h_sum))(ps)
                losses = jax.vmap(_ce, in_axes=(0, None))(logits, yb)
                return _masked_mean(losses, lay.client_mask)

            loss, grads = jax.value_and_grad(total_loss)(params)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, loss

    else:
        # slice/pallas: layer 0 reads only the client's feature slice;
        # per-client grads come from grad(masked sum of per-client
        # losses) -- loss_i depends on params[i] alone (peer terms are
        # stop-gradient'ed), so the stacked gradient IS the per-client
        # gradient stack, and masking drops dead clients' grads
        first = first_layer_fn or make_first_layer_fn(model, pcfg, layout)
        hidden_from = partial(client_hidden_from, model, pcfg.exchange_at)

        def losses_fn(ps, lay, xb, yb, differentiable=None):
            with jax.named_scope("first_layer"):
                h1 = first(ps, xb, lay)
            with jax.named_scope("tower"):
                h_all = jax.vmap(hidden_from)(ps, h1)
            if differentiable is not None:
                h_all = hidden_output_exchange(
                    h_all, differentiable=differentiable,
                    client_mask=lay.client_mask)
            with jax.named_scope("tower"):
                logits = jax.vmap(through)(ps, h_all)
            with jax.named_scope("loss"):
                return jax.vmap(_ce, in_axes=(0, None))(logits, yb)  # [n]

        def devertifl_step(params, opt_state, lay, xb, yb, step_idx):
            def total(ps):
                losses = losses_fn(ps, lay, xb, yb, differentiable=False)
                with jax.named_scope("loss"):
                    return (losses * lay.client_mask).sum(), losses

            grads, losses = jax.grad(total, has_aux=True)(params)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, _masked_mean(losses, lay.client_mask)

        def nonfed_step(params, opt_state, lay, xb, yb, step_idx):
            def total(ps):
                losses = losses_fn(ps, lay, xb, yb)
                with jax.named_scope("loss"):
                    return (losses * lay.client_mask).sum(), losses

            grads, losses = jax.grad(total, has_aux=True)(params)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, _masked_mean(losses, lay.client_mask)

        def verticomb_step(params, opt_state, lay, xb, yb, step_idx):
            def total(ps):
                losses = losses_fn(ps, lay, xb, yb, differentiable=True)
                return _masked_mean(losses, lay.client_mask)

            loss, grads = jax.value_and_grad(total)(params)
            params, opt_state = update(params, opt_state, grads, step_idx)
            return params, opt_state, loss

    return {"devertifl": devertifl_step, "non_federated": nonfed_step,
            "verticomb": verticomb_step}[pcfg.mode]


class PermPlan(NamedTuple):
    """Epoch-shuffle plan from make_perm_fn.  n_dropped documents the
    silent tail drop: each epoch uses n_batches * batch_size samples,
    so the trailing ``n_train % batch_size`` samples of every epoch's
    permutation are discarded (a fresh permutation each epoch means a
    *different* random subset is dropped every epoch, so no sample is
    systematically excluded)."""
    perms: object          # perms(round_key) -> [epochs*n_batches, bs]
    n_batches: int
    batch_size: int
    n_dropped: int         # per-epoch discarded tail = n_train % bs


def make_perm_fn(pcfg, n_train) -> PermPlan:
    """Device-side epoch shuffles: perms(round_key) -> [epochs * n_batches,
    batch_size] int32 batch indices, one independent permutation per
    epoch.

    NOTE the tail-drop semantics: n_batches = n_train // batch_size, so
    the last ``n_train % batch_size`` indices of each epoch permutation
    are dropped (PermPlan.n_dropped).  This matches the common
    drop-last DataLoader behavior and keeps every scanned batch the
    same static shape."""
    bs = min(pcfg.batch_size, n_train)
    n_batches = n_train // bs

    def perms(key):
        keys = jax.random.split(key, pcfg.epochs)
        order = jax.vmap(
            lambda k: jax.random.permutation(k, n_train))(keys)
        return order[:, :n_batches * bs].reshape(
            pcfg.epochs * n_batches, bs)

    return PermPlan(perms, n_batches, bs, n_train - n_batches * bs)


def accepts_client_mask(fn) -> bool:
    """Whether an aggregation fn's signature takes client_mask=."""
    import inspect
    try:
        return "client_mask" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def call_fedavg(fedavg_fn, params, client_mask):
    """Invoke an aggregation fn, passing client_mask only if its
    signature accepts it -- custom aggregators from set_fedavg (e.g.
    the weighted-FedAvg ablation's ``lambda p: ...``) keep working
    unchanged, while the default exchange.fedavg weights by the mask
    so dead padding slots never dilute the average.  On PADDED client
    axes a mask-blind custom aggregator is rejected at build time by
    make_round_fn, never silently mis-averaged."""
    if accepts_client_mask(fedavg_fn):
        return fedavg_fn(params, client_mask=client_mask)
    return fedavg_fn(params)


def make_round_fn(model, opt, pcfg, n_train, fedavg_fn=None, layout=None,
                  first_layer_fn=None, sched_impl=None):
    """One De-VertiFL round as a single jittable function: generate the
    epoch permutations on device, lax.scan the step over every batch of
    every epoch (step_idx carried in the scan), then apply the P2P
    FedAvg (Algorithm 1 lines 16-19) to the carry-out parameters.

    Signature: round_fn(params, opt_state, step_idx, sched_state, key,
    xtr, ytr, lay) -> (params, opt_state, step_idx, sched_state,
    losses[epochs*n_batches]).  sched_state is the exchange-schedule
    carry slot (repro.schedule; ``{}`` for sync -- the sync body is
    the untouched legacy path and merely threads it through).  Data
    (canonical column order) and the LayoutArrays are arguments so a
    sweep can vmap the whole round over a leading lane axis (seeds,
    seeds x client counts on padded layouts, and now schedules).
    fedavg_fn overrides the uniform-mean aggregation (e.g. the
    weighted-FedAvg ablation); it is baked into the jitted round, so
    pass it here rather than patching afterwards.  first_layer_fn is
    forwarded to make_step_fn (padded-sweep override).  sched_impl
    overrides the schedule impl (sweeps pass a lane impl whose ring is
    sized across lanes); by default it resolves from pcfg.schedule.
    """
    plan = make_perm_fn(pcfg, n_train)
    perm_fn = plan.perms
    do_fedavg = pcfg.fedavg and pcfg.mode != "non_federated"
    fedavg_fn = fedavg_fn or fedavg
    padded = (pcfg.max_clients or 0) > pcfg.n_clients or (
        layout is not None and layout.n_real < layout.n_clients)
    if do_fedavg and padded and not accepts_client_mask(fedavg_fn):
        raise ValueError(
            "custom fedavg_fn must accept a client_mask= keyword when "
            "the client axis is padded (max_clients > n_clients): a "
            "mask-blind aggregator would average dead slots' params "
            "into every live client")
    impl = sched_impl
    if impl is None:
        _, impl = resolve_engine(pcfg, model, n_train)

    if impl is None:        # sync: the legacy round, bit-for-bit
        step = make_step_fn(model, opt, pcfg, layout=layout,
                            first_layer_fn=first_layer_fn)

        def round_fn(params, opt_state, step_idx, sched_state, key,
                     xtr, ytr, lay):
            idx = perm_fn(key)

            def body(carry, batch_idx):
                params, opt_state, step_idx = carry
                with jax.named_scope("batch"):
                    xb = jnp.take(xtr, batch_idx, axis=0)
                    yb = jnp.take(ytr, batch_idx, axis=0)
                params, opt_state, loss = step(params, opt_state, lay,
                                               xb, yb, step_idx)
                return (params, opt_state, step_idx + 1), loss

            (params, opt_state, step_idx), losses = jax.lax.scan(
                body, (params, opt_state, step_idx), idx)
            if do_fedavg:
                with jax.named_scope("fedavg"):
                    params = call_fedavg(fedavg_fn, params,
                                         lay.client_mask)
            return params, opt_state, step_idx, sched_state, losses

        return round_fn

    # schedule-aware round: round_start draws the round's effective
    # participation mask, the scan threads the schedule state through
    # every step, FedAvg weights by the round's mask, round_end runs
    # the round-granularity hooks (double_buffer's swap)
    from repro.schedule import make_sched_step_fn
    if do_fedavg and not accepts_client_mask(fedavg_fn):
        raise ValueError(
            "custom fedavg_fn must accept a client_mask= keyword "
            "under a non-sync exchange schedule: the per-round "
            "participation mask weights the aggregation")
    step = make_sched_step_fn(model, opt, pcfg, impl, layout=layout,
                              first_layer_fn=first_layer_fn)
    steps_per_round = pcfg.epochs * plan.n_batches

    def round_fn(params, opt_state, step_idx, sched_state, key,
                 xtr, ytr, lay):
        idx = perm_fn(key)
        round_idx = step_idx // steps_per_round
        sched_state, eff_mask = impl.round_start(sched_state, lay, key,
                                                 round_idx)

        def body(carry, batch_idx):
            params, opt_state, step_idx, sched_state = carry
            with jax.named_scope("batch"):
                xb = jnp.take(xtr, batch_idx, axis=0)
                yb = jnp.take(ytr, batch_idx, axis=0)
            params, opt_state, sched_state, loss = step(
                params, opt_state, lay, eff_mask, sched_state, xb, yb,
                step_idx)
            return (params, opt_state, step_idx + 1, sched_state), loss

        (params, opt_state, step_idx, sched_state), losses = \
            jax.lax.scan(body, (params, opt_state, step_idx,
                                sched_state), idx)
        if do_fedavg:
            # optional fault-layer hook: quarantined clients drop out
            # of the round's aggregation like dead padded slots
            fam = getattr(impl, "fedavg_mask", None)
            mask = eff_mask if fam is None else fam(sched_state,
                                                    eff_mask)
            with jax.named_scope("fedavg"):
                params = call_fedavg(fedavg_fn, params, mask)
        sched_state = impl.round_end(sched_state)
        return params, opt_state, step_idx, sched_state, losses

    return round_fn


def make_h_all_fn(model, pcfg, layout=None, first_layer_fn=None):
    """h_all(params, x, lay) -> [n_clients, B, W] per-client
    activations at the exchange point (logits for exchange_at == -1,
    hidden-layer-k outputs otherwise) from a canonical-order [B, F]
    batch.  This is the per-row half of the inference path: every
    output row depends only on its own input row, which is what lets
    the serving slot pool (repro.serving.federated) batch rows from
    different requests and stay bitwise equal to predict()."""
    fl = resolve_first_layer(pcfg)

    if fl == "masked":
        hidden = partial(client_hidden, model, pcfg.exchange_at)

        def h_all_fn(params, x, lay):
            xm = x[None] * lay.masks[:, None, :]
            with jax.named_scope("tower"):
                return jax.vmap(hidden)(params, xm)
    else:
        first = first_layer_fn or make_first_layer_fn(model, pcfg, layout)
        hidden_from = partial(client_hidden_from, model, pcfg.exchange_at)

        def h_all_fn(params, x, lay):
            with jax.named_scope("first_layer"):
                h1 = first(params, x, lay)
            with jax.named_scope("tower"):
                return jax.vmap(hidden_from)(params, h1)

    return h_all_fn


def make_predict_fn(model, pcfg, layout=None, first_layer_fn=None):
    """predict(params, x, lay) -> [n_clients, B] class predictions.
    x is in canonical column order (Layout.apply).  Dead padded
    clients' rows are garbage -- callers average metrics over the live
    prefix only."""
    through = partial(rest, model, pcfg.exchange_at)
    h_all_fn = make_h_all_fn(model, pcfg, layout=layout,
                             first_layer_fn=first_layer_fn)

    def predict(params, x, lay):
        h_all = h_all_fn(params, x, lay)
        if pcfg.mode in ("devertifl", "verticomb"):
            h_all = hidden_output_exchange(h_all, differentiable=False,
                                           client_mask=lay.client_mask)
        with jax.named_scope("tower"):
            logits = jax.vmap(through)(params, h_all)   # [n, B, C]
        return jnp.argmax(logits, axis=-1)          # per-client preds

    return predict


def train_keys(key):
    """Split a federation key into (init_key, loop_key); round r uses
    fold_in(loop_key, r). Shared by DeVertiFL.train and sweep so a
    sweep lane reproduces the standalone run bit-for-bit."""
    init_key, loop_key = jax.random.split(key)
    return init_key, loop_key


def init_padded_params(model, init_key, n_clients, padded_clients=None):
    """Per-client param stack with a padded client axis.  The LIVE
    clients' keys are ``split(init_key, n_clients)`` -- exactly the
    unpadded derivation, because ``split(key, n)[:k] != split(key, k)``
    and bit-for-bit padding equivalence requires the live inits to
    match.  Dead slots draw from an independent folded key; their
    values never reach a live client (masked out of the exchange and
    FedAvg before the first aggregation)."""
    padded_clients = padded_clients or n_clients
    keys = jax.random.split(init_key, n_clients)
    if padded_clients > n_clients:
        dead = jax.random.split(
            jax.random.fold_in(init_key, np.iinfo(np.int32).max),
            padded_clients - n_clients)
        keys = jnp.concatenate([keys, dead])
    return jax.vmap(model.init)(keys)


# ---------------------------------------------------------------------------
class DeVertiFL:
    """One federation instance: model, partition, per-client params.

    Data is held in the canonical column order of ``self.layout``
    internally; ``predict`` accepts raw (original-column-order) inputs
    and re-expresses them itself.
    """

    def __init__(self, pcfg: ProtocolConfig, fedavg_fn=None,
                 tracer=None):
        from repro.obs.trace import NullTracer
        self.pcfg = pcfg
        self._fedavg_fn = fedavg_fn
        # host spans of evaluate (predict, score); the owning Session
        # passes its own tracer
        self.tracer = tracer if tracer is not None else NullTracer()
        self.mcfg = get_config(arch_for(pcfg.dataset))
        self.model = PaperMLP(self.mcfg)
        xtr, ytr, xte, yte = DR.make_dataset(pcfg.dataset, pcfg.n_samples,
                                             seed=pcfg.seed)
        self.xtr, self.ytr, self.xte, self.yte = xtr, ytr, xte, yte
        self.n_features = self.model.in_features
        self.layout = PT.make_layout(pcfg.dataset, self.n_features,
                                     pcfg.n_clients, seed=pcfg.seed,
                                     max_clients=pcfg.max_clients,
                                     sizes=pcfg.partition_sizes)
        # live clients' ORIGINAL feature ids (dead padding slots are an
        # engine detail; the public partition is the paper's)
        self.partition = self.layout.partition[:pcfg.n_clients]
        self._lay = self.layout.arrays()
        # public masks stay in RAW column order so they compose with the
        # public raw-order xtr/xte (fed.xte * fed.masks[i] is the
        # paper's client view); the engine uses the canonical _lay
        self.masks = jnp.asarray(PT.masks_for(self.partition,
                                              self.n_features))
        self._xtr = jnp.asarray(self.layout.apply(xtr))
        self._xte = jnp.asarray(self.layout.apply(xte))
        self._ytr = jnp.asarray(ytr)
        self.opt = adam(pcfg.lr, max_grad_norm=None)
        self._init_traces = 0
        self._build_steps()

    # ------------------------------------------------------------------
    def init_params(self, key):
        """Initial per-client params (live and padded slots) drawn from
        the init key ``key``.  Compiled, so they equal the ``params`` of
        the set-up program ``_init`` bit for bit; an eager init differs
        from a compiled one by an ulp in places."""
        return self._init_params(key)

    @property
    def init_traces(self) -> int:
        """Trace count of the call set-up program ``_init`` -- 1 after
        any number of training calls on this federation."""
        return self._init_traces

    # ------------------------------------------------------------------
    def _build_steps(self):
        pcfg = self.pcfg
        n_train = len(self.xtr)
        fa = self._fedavg_fn or fedavg
        self._schedule, self._impl = resolve_engine(pcfg, self.model,
                                                    n_train)
        plan = make_perm_fn(pcfg, n_train)
        self.n_batches, self.bs = plan.n_batches, plan.batch_size
        self._steps_per_round = pcfg.epochs * plan.n_batches
        self._perms = jax.jit(plan.perms)
        init_params = partial(init_padded_params, self.model,
                              n_clients=pcfg.n_clients,
                              padded_clients=pcfg.padded_clients)

        def setup(key):
            # a training call's whole set-up as one dispatch: the loop
            # key, params, Adam moments and step index, each output its
            # own buffer for the round to consume by donation
            self._init_traces += 1
            init_key, loop_key = train_keys(key)
            params = init_params(init_key)
            return (loop_key, params, jax.vmap(self.opt.init)(params),
                    jnp.zeros((), jnp.int32))

        self._init = jax.jit(setup)
        self._init_params = jax.jit(init_params)
        self._round = jax.jit(
            make_round_fn(self.model, self.opt, pcfg, n_train,
                          fedavg_fn=fa, layout=self.layout,
                          sched_impl=self._impl),
            donate_argnums=(0, 1))
        self._fedavg = jax.jit(
            lambda p: call_fedavg(fa, p, self._lay.client_mask),
            donate_argnums=(0,))
        self._predict = jax.jit(
            make_predict_fn(self.model, pcfg, layout=self.layout))
        if self._impl is None:
            self._step = jax.jit(
                make_step_fn(self.model, self.opt, pcfg,
                             layout=self.layout),
                donate_argnums=(0, 1))
        else:
            # python-engine pieces for the schedule-aware round: the
            # SAME impl hooks and step builder the scan round bakes
            # in, jitted separately, so both engines stay bit-for-bit
            from repro.schedule import make_sched_step_fn
            self._sched_step = jax.jit(
                make_sched_step_fn(self.model, self.opt, pcfg,
                                   self._impl, layout=self.layout),
                donate_argnums=(0, 1))
            self._round_start = jax.jit(self._impl.round_start)
            self._fedavg_sched = jax.jit(
                lambda p, m: call_fedavg(fa, p, m), donate_argnums=(0,))
            fam = getattr(self._impl, "fedavg_mask", None)
            self._fedavg_mask = None if fam is None else jax.jit(fam)

    def init_sched_state(self):
        """Initial exchange-schedule scan-carry state (``{}`` for the
        sync schedule -- an empty pytree the round threads through)."""
        return {} if self._impl is None else \
            self._impl.init_state(self._schedule)

    def fault_telemetry(self, sched_state):
        """Cumulative fault-event counters carried in the scan state
        (repro.faults), or None when no fault plan is active."""
        tel = getattr(self._impl, "telemetry", None)
        return None if tel is None else tel(sched_state)

    def wire_telemetry(self, sched_state):
        """Cumulative bytes-on-wire counters carried in the scan state
        (repro.wire), or None when no transform is active."""
        tel = getattr(self._impl, "wire_telemetry", None)
        return None if tel is None else tel(sched_state)

    def obs_series(self, sched_state):
        """Per-round metric series carried in the scan state
        (repro.obs), as numpy arrays, or None when obs='none'."""
        ser = getattr(self._impl, "obs_series", None)
        return None if ser is None else ser(sched_state)

    def set_fedavg(self, fedavg_fn):
        """Swap the aggregation function (e.g. weighted FedAvg) and
        rebuild the jitted engines -- FedAvg is baked into the scan
        round, so patching self._fedavg alone would not affect it."""
        self._fedavg_fn = fedavg_fn
        self._build_steps()

    # ------------------------------------------------------------------
    def predict(self, params, x):
        xc = jnp.asarray(self.layout.apply(np.asarray(x)))
        return self._predict(params, xc, self._lay)

    def evaluate(self, params):
        # the test set is already cached in canonical order; skip
        # predict()'s per-call permutation of raw inputs
        with self.tracer.span("predict", cat="eval"):
            preds = np.asarray(self._predict(params, self._xte,
                                             self._lay))
        with self.tracer.span("score", cat="eval"):
            avg = "macro" if len(np.unique(self.ytr)) > 2 else "binary"
            f1s = [f1_score(self.yte, preds[i], average=avg)
                   for i in range(self.pcfg.n_clients)]
            accs = [accuracy(self.yte, preds[i])
                    for i in range(self.pcfg.n_clients)]
        return {"f1": float(np.mean(f1s)), "acc": float(np.mean(accs)),
                "f1_per_client": f1s}

    # ------------------------------------------------------------------
    def _python_round(self, params, opt_state, step_idx, sched_state,
                      key):
        """Pre-refactor reference engine: per-batch host dispatch of the
        jitted step. Consumes the same device permutation stream (and,
        under a non-sync schedule, the same round_start/select/
        round_end hooks) as the scan engine, so trajectories are
        identical."""
        idx = np.asarray(self._perms(key))
        do_avg = self.pcfg.fedavg and self.pcfg.mode != "non_federated"
        losses = []
        if self._impl is None:
            for b in range(idx.shape[0]):
                params, opt_state, loss = self._step(
                    params, opt_state, self._lay,
                    self._xtr[idx[b]], self._ytr[idx[b]], step_idx)
                step_idx = step_idx + 1
                losses.append(loss)
            if do_avg:
                params = self._fedavg(params)
            return params, opt_state, step_idx, sched_state, \
                jnp.stack(losses)
        round_idx = step_idx // self._steps_per_round
        sched_state, eff_mask = self._round_start(sched_state,
                                                  self._lay, key,
                                                  round_idx)
        for b in range(idx.shape[0]):
            params, opt_state, sched_state, loss = self._sched_step(
                params, opt_state, self._lay, eff_mask, sched_state,
                self._xtr[idx[b]], self._ytr[idx[b]], step_idx)
            step_idx = step_idx + 1
            losses.append(loss)
        if do_avg:
            mask = eff_mask if self._fedavg_mask is None else \
                self._fedavg_mask(sched_state, eff_mask)
            params = self._fedavg_sched(params, mask)
        sched_state = self._impl.round_end(sched_state)
        return params, opt_state, step_idx, sched_state, \
            jnp.stack(losses)

    def train(self, key=None, eval_every_round=True, engine=None):
        pcfg = self.pcfg
        engine = engine or pcfg.engine
        key = key if key is not None else jax.random.PRNGKey(pcfg.seed)
        loop_key, params, opt_state, step_idx = self._init(key)
        sched_state = self.init_sched_state()
        history = []
        for r in range(pcfg.rounds):
            rkey = jax.random.fold_in(loop_key, r)
            if engine == "scan":
                params, opt_state, step_idx, sched_state, losses = \
                    self._round(params, opt_state, step_idx,
                                sched_state, rkey,
                                self._xtr, self._ytr, self._lay)
            elif engine == "python":
                params, opt_state, step_idx, sched_state, losses = \
                    self._python_round(params, opt_state, step_idx,
                                       sched_state, rkey)
            else:
                raise ValueError(f"unknown engine {engine!r}")
            if eval_every_round:
                ev = self.evaluate(params)
                ev["round"] = r
                ev["loss"] = float(losses[-1])
                ev["round_losses"] = np.asarray(losses)
                history.append(ev)
        final = self.evaluate(params)
        return {"history": history, "final": final, "params": params}


def train_federation(**kw):
    """DEPRECATED legacy front door, kept as a shim over ``repro.api``.

    Translates ProtocolConfig-style kwargs (``seed=`` becomes the
    spec's ``seeds=(seed,)``) into an ``ExperimentSpec``, runs it
    through ``build(spec).run()``, and returns the historical
    ``{"history", "final", "params"}`` dict -- bit-for-bit what
    ``DeVertiFL(ProtocolConfig(**kw)).train()`` returned
    (tests/test_api.py pins this).  The ``schedule=`` knob forwards
    like every other field and defaults to "sync", so legacy callers
    stay bit-for-bit on the paper-literal engine.  New code should
    construct the spec directly::

        from repro.api import ExperimentSpec, build
        result = build(ExperimentSpec(dataset="mnist", n_clients=5)).run()
    """
    import warnings
    warnings.warn(
        "train_federation(**kw) is deprecated; build an "
        "repro.api.ExperimentSpec and run it via repro.api.build(spec)"
        ".run() instead", DeprecationWarning, stacklevel=2)
    from repro.api import ExperimentSpec, build   # lazy: api sits above core
    kw = dict(kw)
    if "seed" in kw:
        kw["seeds"] = (kw.pop("seed"),)
    rr = build(ExperimentSpec(**kw)).run()
    return {"history": rr.history, "final": rr.metrics,
            "params": rr.params}
