"""Vmapped, sharded grid sweeps over De-VertiFL federations.

Grid semantics
--------------
A sweep is the cartesian grid  datasets x modes x schedules x
client_counts x seeds.  Since PR 3 the engine stacks BOTH the seed
axis and the client-count axis on one leading **lane** axis: every
(n_clients, seed) pair is a lane, all client counts are padded to
``max(client_counts)`` dead slots (``Layout.pad`` -- see
repro.core.partition), and one jitted, vmapped round function from
``repro.core.protocol.make_round_fn`` trains every lane of a
(dataset, mode) cell group simultaneously.  A dataset x mode grid
therefore compiles ONCE across all client counts
(tests/test_padded_engine.py pins the trace count), where previously
every n_clients value was a separate compile.  Since PR 5 the
exchange SCHEDULE (repro.schedule) is a lane axis too: staleness
depth k and participation p ride the traced per-lane schedule state,
so a staleness-tolerance grid (sync / stale_k / partial lanes) also
shares that single compile (tests/test_schedule.py pins it; see
``SweepConfig.schedules`` for the family constraints).

Each lane is an independent federation end to end: its own synthetic
dataset draw, its own vertical partition, its own parameter init
(live clients' init keys are exactly the unpadded derivation -- see
``protocol.init_padded_params``), its own epoch shuffles, all derived
from ``PRNGKey(seed)`` exactly as ``DeVertiFL.train`` derives them.
A masked-lane padded sweep reproduces the corresponding standalone
runs bit-for-bit; the shape-uniform gather-slice first layer (below)
is allclose instead, because its contraction length is padded.

Device scale-out
----------------
Lanes have no cross-lane dataflow, so ``run_padded_cells`` distributes
them over the device mesh with ``jax.shard_map`` under the
``repro.sharding`` rules ("sweep_lane" -> the data-parallel mesh
axes).  The lane axis is split over the largest device count that
divides it; on a single device the shard_map is skipped.  Sharded and
single-device sweeps produce identical results (pinned in
tests/test_padded_engine.py).

First layer under the lane vmap
-------------------------------
Canonical offsets/sizes are static per (dataset, n_clients), so the
per-federation slice/pallas paths close over them -- which is exactly
what a cross-client-count trace cannot do.  The padded sweep instead
uses ``make_uniform_first_layer_fn``: a gather-slice of static width
``max(F_i)`` whose offsets AND sizes ride the traced LayoutArrays,
with out-of-slice columns masked to exact zeros.  first_layer="masked"
keeps the fully-traced zeropad reference (and bitwise standalone
equivalence); "slice"/"pallas"/"auto" resolve to the gather-slice
variant under the lane vmap (a pallas lane needs the scalar-prefetch
offset from the ROADMAP before it can vary offsets per lane).

``run_cell`` (per-count, seed-vmapped only) is retained for
single-cell use -- benchmarks/table2.py and examples drive it --
and as the "looped" baseline the sweep benchmark compares against.
``run_grid`` walks datasets x modes, one padded multi-count batch
each, and returns the same {"cells": {"ds/mode/n": ...}} schema as
before.

See docs/ARCHITECTURE.md for the Layout/LayoutArrays and key
derivation contracts this engine rides on.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro.configs import get_config
from repro.core import partition as PT
from repro.core.protocol import (FIRST_LAYERS, ProtocolConfig, arch_for,
                                 init_padded_params, make_perm_fn,
                                 make_predict_fn, make_round_fn,
                                 resolve_first_layer, train_keys)
from repro.data import registry as DR
from repro.metrics import accuracy, f1_score
from repro.models.mlp_model import PaperMLP
from repro.optim import adam


@dataclass(frozen=True)
class SweepConfig:
    datasets: Sequence[str] = ("mnist", "fmnist", "titanic", "bank")
    modes: Sequence[str] = ("devertifl", "non_federated", "verticomb")
    client_counts: Sequence[int] = (2, 3, 5)
    seeds: Sequence[int] = (0, 1, 2)
    rounds: int = 5
    epochs: int = 5
    batch_size: int = 64
    lr: float = 1e-3
    exchange_at: int = -1
    fedavg: bool = True
    n_samples: Optional[int] = None     # dataset size override (speed)
    first_layer: str = "auto"           # auto | pallas | slice | masked
    # Exchange-schedule lane axis (repro.schedule spec strings).  The
    # sync/stale_k/partial family rides ONE compiled round -- k and p
    # are traced per-lane scalars in the schedule state -- so a
    # staleness-tolerance grid compiles once (round_traces == 1).
    # Non-sync schedules run devertifl mode only; double_buffer and
    # custom schedules cannot share a lane axis with other schedules.
    schedules: Sequence[str] = ("sync",)
    # Fault-plan lane axis (repro.faults spec strings).  Rates,
    # durations and corruption kind ride the traced fault state, so a
    # fault-tolerance grid (none / crash / corrupt lanes) shares the
    # one compiled round too; the straggler ring is sized to the
    # largest delay across lanes.  Non-none plans run devertifl mode
    # only; custom plans cannot ride a lane axis.
    faults: Sequence[str] = ("none",)
    # Exchange-transform lane axis (repro.wire spec strings).  Keep
    # fraction, quantize flag and noise scale ride the traced wire
    # state, so a compression-tradeoff grid (none / topk / int8 / dp
    # lanes) shares the one compiled round as well.  Non-none
    # transforms run devertifl mode only; custom transforms cannot
    # ride a lane axis.
    transforms: Sequence[str] = ("none",)
    # Observability lane axis (repro.obs spec strings).  The level
    # gates ride the traced obs state, so an obs x transform x fault
    # x schedule grid shares the one compiled round too.  Taps are
    # observation-only: a non-none obs lane's trajectory is bitwise
    # its none lane's.  Non-none levels run devertifl mode only;
    # custom obs impls cannot ride a lane axis.
    obs: Sequence[str] = ("none",)


# ---------------------------------------------------------------------------
# shape-uniform first layer for the (seed x client-count) lane vmap
# ---------------------------------------------------------------------------
def make_uniform_first_layer_fn(width: int):
    """first(params, xb, lay) -> [n_clients, B, H] layer-0 activations
    where offsets AND sizes are read from the traced LayoutArrays, so
    a single trace serves lanes with different client counts.

    Client i's slice is gathered as the ``width`` columns starting at
    lay.offsets[i]; columns past lay.sizes[i] are masked to exact
    zeros before the matmul, so they contribute +0.0 terms.  width is
    the max live slice length across all lanes (static).  Because the
    contraction runs over ``width`` terms instead of F_i, results are
    allclose -- not bitwise -- to the per-federation dynamic_slice
    path.  Dead slots (size 0) produce relu(bias), matching the
    per-federation engines' dead_h1."""
    iota = jnp.arange(width)

    def first(params, xb, lay):
        w = params["layer_0"]["kernel"]     # [n, F, H]
        b = params["layer_0"]["bias"]       # [n, H]

        def one(w_i, b_i, off, size):
            valid = (iota < size)
            cols = jnp.where(valid, off + iota, 0)
            x_i = xb[:, cols] * valid.astype(xb.dtype)[None, :]
            return jax.nn.relu(x_i @ w_i[cols] + b_i)

        return jax.vmap(one)(w, b, lay.offsets, lay.sizes)
    return first


def _sweep_first_layer(pcfg, width):
    """Resolve the first layer for a lane-vmapped sweep: masked stays
    masked (fully traced already); slice/pallas/auto take the uniform
    gather-slice (static pallas offsets cannot vary across lanes).
    Registered custom backends close over per-federation statics the
    lane vmap cannot vary, so they are refused here, not mis-traced."""
    fl = resolve_first_layer(pcfg)
    if FIRST_LAYERS.get(fl) is not None:
        raise ValueError(
            f"custom first_layer {fl!r} is not supported in padded "
            "multi-count sweeps (its offsets/sizes cannot vary per "
            "lane); use 'masked', 'slice', 'pallas', or 'auto'")
    if fl == "masked":
        return None
    return make_uniform_first_layer_fn(width)


# ---------------------------------------------------------------------------
# exchange-schedule lanes
# ---------------------------------------------------------------------------
def _sweep_schedules(scfg, mode, model, n_clients, n_train):
    """Parse scfg.schedules into (scheds, impl, sync_only) for a lane
    batch of one (dataset, mode).  sync-only sweeps get impl=None (the
    untouched legacy round).  Mixed schedule lanes must all belong to
    the sync/stale_k/partial family: k and p ride the traced schedule
    state, so ONE ring impl (sized to the largest k) serves every
    lane under a single trace.  double_buffer is vmappable but carries
    a differently-shaped state, so it cannot share an axis with other
    schedules; custom schedules (like custom first layers) may close
    over per-federation statics and are refused outright."""
    from repro.schedule import get_schedule, make_schedule_impl
    if not scfg.schedules:
        raise ValueError("schedules must name at least one schedule")
    scheds = tuple(get_schedule(s) for s in scfg.schedules)
    if len(scheds) == 1 and scheds[0].is_sync:
        return scheds, None, True
    if mode != "devertifl":
        raise ValueError(
            f"schedules beyond 'sync' require mode='devertifl' sweep "
            f"cells, got mode {mode!r}")
    if any(s.custom is not None for s in scheds):
        raise ValueError(
            "custom schedules are not supported in sweep lanes (their "
            "impls may close over per-federation statics the lane "
            "vmap cannot vary); run them as standalone sessions")
    if any(s.double_buffer for s in scheds) and len(scheds) > 1:
        raise ValueError(
            "double_buffer carries a differently-shaped schedule "
            "state and cannot share a lane axis with other schedules; "
            "sweep it as its own single-schedule batch")
    from repro.core.protocol import exchange_width
    impl = make_schedule_impl(
        scheds[0], n_clients, min(scfg.batch_size, n_train),
        exchange_width(model, scfg.exchange_at),
        max_k=max(s.k for s in scheds))
    return scheds, impl, False


def _stacked_sched_state(impl, scheds, n_base):
    """Per-lane initial schedule states, schedule-major over a base
    lane batch of n_base (count x seed) lanes."""
    if impl is None:
        return {}
    per = [jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_base,) + a.shape),
        impl.init_state(sc)) for sc in scheds]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *per)


# ---------------------------------------------------------------------------
# fault-plan lanes
# ---------------------------------------------------------------------------
def _sweep_faults(scfg, mode, model, n_clients, n_train, impl):
    """Parse scfg.faults into (plans, impl, none_only) for a lane batch
    of one (dataset, mode).  A none-only axis hands the schedule impl
    back untouched -- the fault-free sweep is bit-for-bit the pre-fault
    one.  Mixed fault lanes share ONE FaultImpl: rates / durations /
    corruption kind are traced per-lane state, and the straggler ring
    is sized to the largest delay across lanes.  Literal sync under a
    fault axis is promoted to the depth-0 ring impl (proven
    bitwise-sync) so the fault layer has four-hook state to ride;
    custom plans (like custom schedules) may close over per-federation
    statics and are refused."""
    from repro.faults import get_fault_plan, make_fault_impl
    if not scfg.faults:
        raise ValueError("faults must name at least one fault plan")
    plans = tuple(get_fault_plan(f) for f in scfg.faults)
    if len(plans) == 1 and plans[0].is_none:
        return plans, impl, True
    if mode != "devertifl":
        raise ValueError(
            f"fault plans beyond 'none' require mode='devertifl' sweep "
            f"cells, got mode {mode!r}")
    if any(p.custom is not None for p in plans):
        raise ValueError(
            "custom fault plans are not supported in sweep lanes "
            "(their impls may close over per-federation statics the "
            "lane vmap cannot vary); run them as standalone sessions")
    from repro.core.protocol import exchange_width
    bs = min(scfg.batch_size, n_train)
    width = exchange_width(model, scfg.exchange_at)
    if impl is None:
        from repro.schedule import LaneScheduleImpl
        impl = LaneScheduleImpl(0, n_clients, bs, width)
    impl = make_fault_impl(plans[0], impl, n_clients, bs, width,
                           max_delay=max(p.max_delay for p in plans))
    return plans, impl, False


def _stacked_fault_state(impl, plans, scheds, n_base, none_only):
    """Per-lane initial carry states, fault-major over the
    schedule-major base ((plan, sched) blocks of n_base lanes each).
    A none-only fault axis reduces to :func:`_stacked_sched_state`."""
    if none_only:
        return _stacked_sched_state(impl, scheds, n_base)
    per = [jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_base,) + a.shape),
        impl.init_state(sc, plan=pl))
        for pl in plans for sc in scheds]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *per)


# ---------------------------------------------------------------------------
# exchange-transform (wire) lanes
# ---------------------------------------------------------------------------
def _sweep_transforms(scfg, mode, model, n_clients, n_train, impl):
    """Parse scfg.transforms into (wires, impl, none_only) for a lane
    batch of one (dataset, mode).  A none-only axis hands the
    schedule/fault impl back untouched -- the transform-free sweep is
    bit-for-bit the pre-wire one.  Mixed transform lanes share ONE
    WireImpl: keep fraction, quantize flag and noise scale are traced
    per-lane state, so transform x fault x schedule grids ride the
    single compiled round.  Like the fault layer, literal sync under a
    wire axis is promoted to the depth-0 ring impl so the wire layer
    has four-hook state to wrap; custom transforms may close over
    per-federation statics and are refused."""
    from repro.wire import get_wire_plan, make_wire_impl
    if not scfg.transforms:
        raise ValueError("transforms must name at least one transform")
    wires = tuple(get_wire_plan(t) for t in scfg.transforms)
    if len(wires) == 1 and wires[0].is_none:
        return wires, impl, True
    if mode != "devertifl":
        raise ValueError(
            f"transforms beyond 'none' require mode='devertifl' sweep "
            f"cells, got mode {mode!r}")
    if any(w.custom is not None for w in wires):
        raise ValueError(
            "custom transforms are not supported in sweep lanes (their "
            "impls may close over per-federation statics the lane "
            "vmap cannot vary); run them as standalone sessions")
    from repro.core.protocol import exchange_width
    bs = min(scfg.batch_size, n_train)
    width = exchange_width(model, scfg.exchange_at)
    if impl is None:
        from repro.schedule import LaneScheduleImpl
        impl = LaneScheduleImpl(0, n_clients, bs, width)
    impl = make_wire_impl(wires[0], impl, n_clients, bs, width)
    return wires, impl, False


def _stacked_wire_state(impl, wires, plans, scheds, n_base,
                        fault_none_only, wire_none_only):
    """Per-lane initial carry states, transform-major over the
    fault-major-over-schedule-major base ((wire, plan, sched) blocks of
    n_base lanes each).  A none-only wire axis reduces to
    :func:`_stacked_fault_state`."""
    if wire_none_only:
        return _stacked_fault_state(impl, plans, scheds, n_base,
                                    fault_none_only)
    per = []
    for wp in wires:
        for pl in plans:
            kw = {"wire": wp}
            if not fault_none_only:
                kw["plan"] = pl
            for sc in scheds:
                per.append(jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_base,) + a.shape),
                    impl.init_state(sc, **kw)))
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *per)


# ---------------------------------------------------------------------------
# observability (obs) lanes
# ---------------------------------------------------------------------------
def _sweep_obs(scfg, mode, model, n_clients, n_train, impl):
    """Parse scfg.obs into (obss, impl, none_only) for a lane batch of
    one (dataset, mode).  A none-only axis hands the
    schedule/fault/wire impl back untouched -- the obs-free sweep is
    bit-for-bit the pre-obs one.  Mixed obs lanes share ONE ObsImpl:
    the level gates are traced per-lane state, so obs x transform x
    fault x schedule grids ride the single compiled round.  Like the
    fault and wire layers, literal sync under an obs axis is promoted
    to the depth-0 ring impl so the taps have four-hook state to
    wrap; custom obs impls may close over per-federation statics and
    are refused."""
    from repro.obs import get_obs_plan, make_obs_impl
    if not scfg.obs:
        raise ValueError("obs must name at least one obs level")
    obss = tuple(get_obs_plan(o) for o in scfg.obs)
    if len(obss) == 1 and obss[0].is_none:
        return obss, impl, True
    if mode != "devertifl":
        raise ValueError(
            f"obs levels beyond 'none' require mode='devertifl' sweep "
            f"cells, got mode {mode!r}")
    if any(o.custom is not None for o in obss):
        raise ValueError(
            "custom obs impls are not supported in sweep lanes (their "
            "impls may close over per-federation statics the lane "
            "vmap cannot vary); run them as standalone sessions")
    from repro.core.protocol import exchange_width
    bs = min(scfg.batch_size, n_train)
    width = exchange_width(model, scfg.exchange_at)
    if impl is None:
        from repro.schedule import LaneScheduleImpl
        impl = LaneScheduleImpl(0, n_clients, bs, width)
    # build at the HIGHEST stacked level: tap work above the impl's
    # static level is not traced at all, and every lane must share
    # one trace -- lower-level lanes gate it off with traced zeros
    top = max(obss, key=lambda o: o.level)
    impl = make_obs_impl(top, impl, n_clients, bs, width,
                         rounds=scfg.rounds)
    return obss, impl, False


def _stacked_obs_state(impl, obss, wires, plans, scheds, n_base,
                       fault_none_only, wire_none_only,
                       obs_none_only):
    """Per-lane initial carry states, obs-major over the
    transform-major-over-fault-major-over-schedule-major base ((obs,
    wire, plan, sched) blocks of n_base lanes each).  A none-only obs
    axis reduces to :func:`_stacked_wire_state`."""
    if obs_none_only:
        return _stacked_wire_state(impl, wires, plans, scheds, n_base,
                                   fault_none_only, wire_none_only)
    per = []
    for op in obss:
        for wp in wires:
            for pl in plans:
                kw = {"obs": op}
                if not wire_none_only:
                    kw["wire"] = wp
                if not fault_none_only:
                    kw["plan"] = pl
                for sc in scheds:
                    per.append(jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a, (n_base,) + a.shape),
                        impl.init_state(sc, **kw)))
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *per)


# ---------------------------------------------------------------------------
# lane stacking
# ---------------------------------------------------------------------------
def _stacked_federations(dataset, n_clients, seeds, n_samples):
    """Per-seed datasets, canonical layouts and keys stacked on axis 0.
    Data is permuted into each seed's canonical column order; the
    LayoutArrays (masks/offsets/sizes/client_mask) carry the per-seed
    layout through the vmapped round."""
    xtr, ytr, xte, yte = DR.make_dataset_stack(dataset, seeds, n=n_samples)
    layouts = [PT.make_layout(dataset, xtr.shape[-1], n_clients, seed=s)
               for s in seeds]
    # canonical offsets/sizes are seed-independent (only the column
    # assignment varies); the pallas path relies on this to close over
    # static offsets under the seed vmap
    if any(l.offsets != layouts[0].offsets or l.sizes != layouts[0].sizes
           for l in layouts):
        raise ValueError("per-seed canonical layouts disagree on "
                         "offsets/sizes; the static-offset pallas path "
                         "cannot be vmapped over such lanes")
    xtr = jnp.asarray(np.stack([l.apply(x) for x, l in zip(xtr, layouts)]))
    xte = jnp.asarray(np.stack([l.apply(x) for x, l in zip(xte, layouts)]))
    ytr, yte = jnp.asarray(ytr), jnp.asarray(yte)
    lay = jax.tree.map(lambda *a: jnp.stack(a),
                       *[l.arrays() for l in layouts])
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    return xtr, ytr, xte, yte, lay, keys, layouts[0]


def _stacked_lanes(dataset, client_counts, seeds, n_samples,
                   max_clients=None):
    """Stack every (n_clients, seed) pair on one lane axis, padded to
    max(client_counts) (or an explicit wider ``max_clients``).
    Returns (xtr, ytr, xte, yte, lay, keys, lanes, width): lanes is
    the [(n_clients, seed), ...] order (count-major), width the max
    live slice length."""
    max_c = max_clients or max(client_counts)
    xtr, ytr, xte, yte = DR.make_dataset_stack(dataset, seeds, n=n_samples)
    xs_tr, xs_te, lays, lanes, width = [], [], [], [], 1
    for nc in client_counts:
        for si, s in enumerate(seeds):
            lo = PT.make_layout(dataset, xtr.shape[-1], nc, seed=s,
                                max_clients=max_c)
            lanes.append((nc, s))
            width = max(width, max(lo.sizes))
            xs_tr.append(lo.apply(xtr[si]))
            xs_te.append(lo.apply(xte[si]))
            lays.append(lo.arrays())
    n_rep = len(client_counts)
    lay = jax.tree.map(lambda *a: jnp.stack(a), *lays)
    keys = jnp.stack([jax.random.PRNGKey(s) for _, s in lanes])
    return (jnp.asarray(np.stack(xs_tr)),
            jnp.asarray(np.concatenate([ytr] * n_rep)),
            jnp.asarray(np.stack(xs_te)),
            jnp.asarray(np.concatenate([yte] * n_rep)),
            lay, keys, lanes, width)


def _lane_metrics(preds, yte, ytr, lanes):
    """Per-lane mean-over-live-clients F1/acc from padded predictions
    [L, max_clients, B_test]."""
    f1s, accs = [], []
    for li, (nc, _) in enumerate(lanes):
        avg = "macro" if len(np.unique(ytr[li])) > 2 else "binary"
        f1s.append(float(np.mean([f1_score(yte[li], preds[li, i],
                                           average=avg)
                                  for i in range(nc)])))
        accs.append(float(np.mean([accuracy(yte[li], preds[li, i])
                                   for i in range(nc)])))
    return f1s, accs


def _train_rounds(vround, vfold, params, opt_state, sched_state,
                  loop_keys, xtr, ytr, lay, rounds):
    """Drive `rounds` vmapped rounds and time STEADY STATE only: round
    0 triggers the jit compile, so the clock restarts after it (with
    rounds == 1 the compile is unavoidably included -- matching
    benchmarks/protocol_bench's warmed-up timings).  Shared by
    run_cell and run_padded_cells so the looped-vs-padded benchmark
    comparison can never diverge on timing protocol.  sched_state is
    the per-lane exchange-schedule(+fault) carry ({} for sync).
    Returns (params, opt_state, sched_state, losses, wall,
    timed_rounds) -- the final carry is returned so fault telemetry
    counters can be read back per lane."""
    step_idx = jnp.zeros((loop_keys.shape[0],), jnp.int32)
    t0 = time.perf_counter()
    losses = None
    timed_rounds = rounds
    for r in range(rounds):
        params, opt_state, step_idx, sched_state, losses = vround(
            params, opt_state, step_idx, sched_state,
            vfold(loop_keys, r), xtr, ytr, lay)
        if r == 0 and rounds > 1:
            jax.block_until_ready(losses)
            t0 = time.perf_counter()
            timed_rounds = rounds - 1
    jax.block_until_ready(losses)
    return (params, opt_state, sched_state, losses,
            time.perf_counter() - t0, timed_rounds)


# ---------------------------------------------------------------------------
# single-cell (per-count) runner -- the pre-padding engine, retained
# ---------------------------------------------------------------------------
def run_cell(dataset, mode, n_clients, scfg: SweepConfig):
    """Train len(scfg.seeds) federations of one (dataset, mode,
    n_clients) cell in a single vmapped computation.  One compile per
    (dataset, mode, n_clients): the looped baseline the padded
    multi-count engine (run_padded_cells) is benchmarked against."""
    if len(scfg.schedules) != 1:
        raise ValueError(
            "run_cell takes exactly one schedule; use "
            "run_padded_cells(schedules=...) for schedule grids")
    if len(scfg.faults) != 1:
        raise ValueError(
            "run_cell takes exactly one fault plan; use "
            "run_padded_cells(faults=...) for fault grids")
    if len(scfg.transforms) != 1:
        raise ValueError(
            "run_cell takes exactly one transform; use "
            "run_padded_cells(transforms=...) for wire grids")
    if len(scfg.obs) != 1:
        raise ValueError(
            "run_cell takes exactly one obs level; use "
            "run_padded_cells(obs=...) for obs grids")
    pcfg = ProtocolConfig(
        dataset=dataset, n_clients=n_clients, rounds=scfg.rounds,
        epochs=scfg.epochs, batch_size=scfg.batch_size, lr=scfg.lr,
        exchange_at=scfg.exchange_at, mode=mode, fedavg=scfg.fedavg,
        n_samples=scfg.n_samples, first_layer=scfg.first_layer,
        schedule=scfg.schedules[0], fault=scfg.faults[0],
        transform=scfg.transforms[0], obs=scfg.obs[0])
    model = PaperMLP(get_config(arch_for(dataset)))
    opt = adam(pcfg.lr, max_grad_norm=None)

    xtr, ytr, xte, yte, lay, keys, layout = _stacked_federations(
        dataset, n_clients, scfg.seeds, scfg.n_samples)
    n_seeds, n_train = xtr.shape[0], xtr.shape[1]
    scheds, impl, _ = _sweep_schedules(scfg, mode, model, n_clients,
                                       n_train)
    plans, impl, none_only = _sweep_faults(scfg, mode, model, n_clients,
                                           n_train, impl)
    wires, impl, wire_none = _sweep_transforms(scfg, mode, model,
                                               n_clients, n_train, impl)
    obss, impl, obs_none = _sweep_obs(scfg, mode, model, n_clients,
                                      n_train, impl)
    sched_state = _stacked_obs_state(impl, obss, wires, plans, scheds,
                                     n_seeds, none_only, wire_none,
                                     obs_none)

    def init_one(key):
        init_key, loop_key = train_keys(key)
        ks = jax.random.split(init_key, n_clients)
        params = jax.vmap(model.init)(ks)
        return params, jax.vmap(opt.init)(params), loop_key

    params, opt_state, loop_keys = jax.jit(jax.vmap(init_one))(keys)

    round_fn = make_round_fn(model, opt, pcfg, n_train, layout=layout,
                             sched_impl=impl)
    vround = jax.jit(jax.vmap(round_fn), donate_argnums=(0, 1))
    vpred = jax.jit(jax.vmap(make_predict_fn(model, pcfg, layout=layout)))
    vfold = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(0, None)))

    params, opt_state, sched_state, losses, wall, timed_rounds = \
        _train_rounds(vround, vfold, params, opt_state, sched_state,
                      loop_keys, xtr, ytr, lay, pcfg.rounds)

    preds = np.asarray(vpred(params, xte, lay))      # [S, n, B_test]
    yte_np, ytr_np = np.asarray(yte), np.asarray(ytr)
    f1s, accs = _lane_metrics(preds, yte_np, ytr_np,
                              [(n_clients, s) for s in scfg.seeds])
    steps = timed_rounds * pcfg.epochs * make_perm_fn(pcfg,
                                                      n_train).n_batches
    cell = {
        "dataset": dataset, "mode": mode, "n_clients": n_clients,
        "seeds": list(scfg.seeds),
        "f1_per_seed": f1s, "acc_per_seed": accs,
        "f1_mean": float(np.mean(f1s)), "f1_std": float(np.std(f1s)),
        "acc_mean": float(np.mean(accs)),
        "final_loss_mean": float(np.asarray(losses)[:, -1].mean()),
        "wall_s": wall,
        "steps_per_sec": steps * n_seeds / max(wall, 1e-9),
    }
    if not none_only:
        cell["fault"] = plans[0].spec
        tel = impl.telemetry(sched_state)
        cell["fault_telemetry"] = {k: int(np.sum(v))
                                   for k, v in tel.items()}
    if not wire_none:
        cell["transform"] = wires[0].spec
        wtel = impl.wire_telemetry(sched_state)
        cell["wire"] = {k: int(np.sum(v)) for k, v in wtel.items()}
    if not obs_none:
        cell["obs"] = obss[0].spec
        # per-round series with a leading seed axis [S, R, ...]
        cell["obs_series"] = impl.obs_series(sched_state)
    return cell


# ---------------------------------------------------------------------------
# padded multi-count engine: one compile per (dataset, mode), lanes
# sharded over the device mesh
# ---------------------------------------------------------------------------
def _lane_shards(n_lanes: int, shard) -> int:
    """How many devices to split the lane axis over: the largest
    available device count dividing n_lanes (1 = no shard_map).
    shard=False forces single-device; an int requests that many."""
    if shard is False:
        return 1
    avail = jax.device_count()
    if isinstance(shard, int) and not isinstance(shard, bool):
        if n_lanes % shard or shard > avail:
            raise ValueError(f"cannot shard {n_lanes} lanes over "
                             f"{shard} of {avail} devices")
        return shard
    return max(d for d in range(1, avail + 1) if n_lanes % d == 0)


def _coerce_sweep_config(dataset, mode, scfg):
    """Let run_padded_cells take a spec grid in place of a SweepConfig:
    a sequence of ``repro.api.ExperimentSpec`` (one per client count,
    same dataset/mode) is translated via the api layer.  Returns the
    (dataset, internal_mode, SweepConfig) triple."""
    if isinstance(scfg, SweepConfig):
        return dataset, mode, scfg
    from repro.api.modes import get_mode        # lazy: api > core
    from repro.api.session import sweep_config_for_specs
    ds, internal, cfg = sweep_config_for_specs(scfg)
    if dataset is not None and dataset != ds:
        raise ValueError(f"dataset argument {dataset!r} does not match "
                         f"the specs' dataset {ds!r}")
    # resolve the caller's mode through the registry so aliases
    # (backward_exchange == verticomb) compare equal
    if mode is not None and get_mode(mode).internal != internal:
        raise ValueError(f"mode argument {mode!r} does not match the "
                         f"specs' mode {internal!r}")
    return ds, internal, cfg


class LaneBatch(NamedTuple):
    """One fully-assembled sweep lane batch: the vmappable round and
    every per-lane tensor it consumes.  ``build_lane_batch`` is the
    single assembly path shared by :func:`run_padded_cells` (which
    trains it) and the static auditor's retrace pass
    (``repro.analysis.retrace``, which re-traces sub-batches and
    compares jaxprs -- the static side of the compile-once claim)."""
    pcfg: ProtocolConfig
    model: object
    opt: object
    round_fn: object            # un-jitted, per-lane; vmap to train
    first: object               # shape-uniform first layer (or None)
    params: object
    opt_state: object
    sched_state: object
    loop_keys: object
    xtr: object
    ytr: object
    xte: object
    yte: object
    lay: object
    lanes: tuple                # [(n_clients, seed), ...] wire-major
    scheds: tuple               # then fault- then sched-major blocks
    sync_only: bool
    n_train: int
    n_base: int                 # lanes per (wire, fault, sched) block
    width: int
    plans: tuple = ()           # parsed FaultPlans (fault lane axis)
    none_only: bool = True      # fault axis is the default ("none",)
    impl: object = None         # the resolved lane impl (None = sync)
    wires: tuple = ()           # parsed WirePlans (transform lane axis)
    wire_none_only: bool = True  # wire axis is the default ("none",)
    obss: tuple = ()            # parsed ObsPlans (obs lane axis)
    obs_none_only: bool = True  # obs axis is the default ("none",)

    @property
    def n_lanes(self) -> int:
        return len(self.lanes)


def build_lane_batch(dataset, mode, scfg: SweepConfig,
                     max_clients=None, width=None) -> LaneBatch:
    """Assemble the transforms x faults x schedules x client_counts x
    seeds lane batch of one (dataset, mode) pair: stacked
    data/layouts/keys, per-count padded inits,
    wire-major-over-fault-major-over-schedule-major tiling, and the
    single un-jitted round function every lane shares.
    ``max_clients`` widens the padded client axis beyond
    max(client_counts) and ``width`` widens the gather-slice first
    layer -- the auditor pins both so sub-batches that must share a
    compile stay shape-identical."""
    counts = tuple(scfg.client_counts)
    max_c = max_clients or max(counts)
    if max_c < max(counts):
        raise ValueError(f"max_clients={max_c} < max client count "
                         f"{max(counts)}")
    # n_clients=min(counts) keeps ProtocolConfig's padded/unpadded
    # distinction truthful (lanes carry n_real in [min, max]), so
    # make_round_fn's mask-blind-aggregator guard stays armed whenever
    # any lane actually has dead slots
    pcfg = ProtocolConfig(
        dataset=dataset, n_clients=min(counts), max_clients=max_c,
        rounds=scfg.rounds, epochs=scfg.epochs,
        batch_size=scfg.batch_size, lr=scfg.lr,
        exchange_at=scfg.exchange_at, mode=mode, fedavg=scfg.fedavg,
        n_samples=scfg.n_samples, first_layer=scfg.first_layer)
    model = PaperMLP(get_config(arch_for(dataset)))
    opt = adam(pcfg.lr, max_grad_norm=None)

    xtr, ytr, xte, yte, lay, keys, base_lanes, data_width = \
        _stacked_lanes(dataset, counts, scfg.seeds, scfg.n_samples,
                       max_clients=max_c)
    width = max(width or 0, data_width)
    n_base, n_train = xtr.shape[0], xtr.shape[1]
    first = _sweep_first_layer(pcfg, width)
    scheds, impl, sync_only = _sweep_schedules(scfg, mode, model,
                                               max_c, n_train)
    plans, impl, none_only = _sweep_faults(scfg, mode, model, max_c,
                                           n_train, impl)
    wires, impl, wire_none = _sweep_transforms(scfg, mode, model,
                                               max_c, n_train, impl)
    obss, impl, obs_none = _sweep_obs(scfg, mode, model, max_c,
                                      n_train, impl)
    n_sched, n_fault = len(scheds), len(plans)
    n_wire, n_obs = len(wires), len(obss)

    # per-count init (live keys must be split(init_key, nc) -- a
    # count-static derivation -- so init compiles once per count;
    # only the ROUND is the compile-once claim)
    ps, os_, lks = [], [], []
    for ci, nc in enumerate(counts):
        def init_one(key, nc=nc):
            init_key, loop_key = train_keys(key)
            params = init_padded_params(model, init_key, nc, max_c)
            return params, jax.vmap(opt.init)(params), loop_key
        s = len(scfg.seeds)
        p, o, lk = jax.jit(jax.vmap(init_one))(keys[ci * s:(ci + 1) * s])
        ps.append(p), os_.append(o), lks.append(lk)
    params = jax.tree.map(lambda *a: jnp.concatenate(a), *ps)
    opt_state = jax.tree.map(lambda *a: jnp.concatenate(a), *os_)
    loop_keys = jnp.concatenate(lks)

    # obs-major-over-wire-major-over-fault-major-over-schedule-major
    # lane tiling: every (obs, wire, fault, schedule) tuple reuses the
    # SAME (count x seed) base batch -- same data, same layouts, same
    # inits, same key streams -- and differs only in the per-lane
    # carry state (traced k / p / rates / keep fractions / level
    # gates + buffers)
    n_tile = n_obs * n_wire * n_fault * n_sched
    if n_tile > 1:
        def tile(a):
            return jnp.concatenate([a] * n_tile, 0)
        xtr, ytr, xte, yte = map(tile, (xtr, ytr, xte, yte))
        lay = jax.tree.map(tile, lay)
        loop_keys = tile(loop_keys)
        params = jax.tree.map(tile, params)
        opt_state = jax.tree.map(tile, opt_state)
    sched_state = _stacked_obs_state(impl, obss, wires, plans, scheds,
                                     n_base, none_only, wire_none,
                                     obs_none)
    lanes = tuple((nc, s) for _ in obss for _ in wires for _ in plans
                  for _ in scheds for (nc, s) in base_lanes)

    round_fn = make_round_fn(model, opt, pcfg, n_train,
                             first_layer_fn=first, sched_impl=impl)
    return LaneBatch(pcfg=pcfg, model=model, opt=opt,
                     round_fn=round_fn, first=first, params=params,
                     opt_state=opt_state, sched_state=sched_state,
                     loop_keys=loop_keys, xtr=xtr, ytr=ytr, xte=xte,
                     yte=yte, lay=lay, lanes=lanes, scheds=scheds,
                     sync_only=sync_only, n_train=n_train,
                     n_base=n_base, width=width, plans=plans,
                     none_only=none_only, impl=impl, wires=wires,
                     wire_none_only=wire_none, obss=obss,
                     obs_none_only=obs_none)


def run_padded_cells(dataset, mode, scfg, shard="auto"):
    """Train the FULL schedules x client_counts x seeds lane batch of
    one (dataset, mode) pair under a single compiled round function,
    distributing lanes over the device mesh.  ``scfg`` is a
    SweepConfig, or a sequence of ``repro.api.ExperimentSpec`` sharing
    one (dataset, mode) whose n_clients / schedule values form the
    count and schedule axes.

    Returns {"cells": {key: cell_dict}, "round_traces": int,
    "lanes": int, "devices": int, "wall_s": float, "cells_per_sec":
    float, "steps_per_sec": float}.  For the default sync-only
    schedule axis the cell keys stay the historical bare ``n_clients``
    ints; a non-default schedule axis keys cells as
    ``"{schedule}/{n_clients}"`` (e.g. ``"stale_k:2/3"``); a
    non-default fault axis prepends the plan
    (``"{fault}/{schedule}/{n_clients}"``); a non-default transform
    axis prepends the wire spec on top
    (``"{transform}/{fault}/{schedule}/{n_clients}"``); a non-default
    obs axis prepends the level on top of everything
    (``"{obs}/{transform}/{fault}/{schedule}/{n_clients}"``).  Each
    cell_dict has the run_cell schema plus ``"schedule"`` (under a
    fault axis, ``"fault"`` + per-cell ``"fault_telemetry"`` event
    counts summed over seeds; under a transform axis, ``"transform"``
    + per-cell ``"wire"`` integer bytes-on-wire summed over seeds;
    under an obs axis, ``"obs"`` + per-cell ``"obs_series"``
    per-round series with a leading seed axis)
    -- except that wall_s is the SHARED batch wall and
    each cell's steps_per_sec is its lanes' share of it (cells sum to
    the batch's steps_per_sec).  round_traces counts actual retraces
    of the round body -- 1 means the whole multi-count (and
    multi-schedule / multi-fault: k, p and fault rates are traced
    per-lane state) batch ran on one compile (pinned in tests;
    ``repro.analysis``'s retrace pass proves the static side).
    shard: "auto" (largest dividing device count) | False | int.
    """
    dataset, mode, scfg = _coerce_sweep_config(dataset, mode, scfg)
    lb = build_lane_batch(dataset, mode, scfg)
    pcfg, scheds, counts = lb.pcfg, lb.scheds, tuple(scfg.client_counts)
    n_base, n_train, n_lanes = lb.n_base, lb.n_train, lb.n_lanes
    params, opt_state, sched_state = (lb.params, lb.opt_state,
                                      lb.sched_state)
    loop_keys, xtr, ytr, xte, yte, lay = (lb.loop_keys, lb.xtr, lb.ytr,
                                          lb.xte, lb.yte, lb.lay)
    round_fn, lanes, sync_only = lb.round_fn, lb.lanes, lb.sync_only
    plans, none_only = lb.plans, lb.none_only
    wires, wire_none = lb.wires, lb.wire_none_only
    obss, obs_none = lb.obss, lb.obs_none_only
    traces = 0

    def counted_round(*args):
        nonlocal traces
        traces += 1
        return round_fn(*args)

    vround = jax.vmap(counted_round)
    n_dev = _lane_shards(n_lanes, shard)
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        with sh.use_context(mesh):
            spec = sh.logical_spec("sweep_lane")    # -> P("data")
        vround = jax.shard_map(vround, mesh=mesh, in_specs=(spec,) * 8,
                               out_specs=spec, check_vma=False)
    vround = jax.jit(vround, donate_argnums=(0, 1))
    vpred = jax.jit(jax.vmap(
        make_predict_fn(lb.model, pcfg, first_layer_fn=lb.first)))
    vfold = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(0, None)))

    params, opt_state, sched_state, losses, wall, timed_rounds = \
        _train_rounds(vround, vfold, params, opt_state, sched_state,
                      loop_keys, xtr, ytr, lay, pcfg.rounds)

    preds = np.asarray(vpred(params, xte, lay))   # [L, max_c, B_test]
    yte_np, ytr_np = np.asarray(yte), np.asarray(ytr)
    f1s, accs = _lane_metrics(preds, yte_np, ytr_np, lanes)
    losses_np = np.asarray(losses)
    steps = timed_rounds * pcfg.epochs * make_perm_fn(pcfg,
                                                      n_train).n_batches
    cells = {}
    s = len(scfg.seeds)
    for oi, op in enumerate(obss):
        for wi, wp in enumerate(wires):
            for fi, pl in enumerate(plans):
                for si, sc in enumerate(scheds):
                    for ci, nc in enumerate(counts):
                        lo = (((oi * len(wires) + wi) * len(plans)
                               + fi) * len(scheds)
                              + si) * n_base + ci * s
                        sl = slice(lo, lo + s)
                        if not obs_none:
                            ck = (f"{op.spec}/{wp.spec}/{pl.spec}/"
                                  f"{sc.spec}/{nc}")
                        elif not wire_none:
                            ck = f"{wp.spec}/{pl.spec}/{sc.spec}/{nc}"
                        elif not none_only:
                            ck = f"{pl.spec}/{sc.spec}/{nc}"
                        elif not sync_only:
                            ck = f"{sc.spec}/{nc}"
                        else:
                            ck = nc
                        cell = {
                            "dataset": dataset, "mode": mode,
                            "n_clients": nc,
                            "schedule": sc.spec,
                            "seeds": list(scfg.seeds),
                            "f1_per_seed": f1s[sl],
                            "acc_per_seed": accs[sl],
                            "f1_mean": float(np.mean(f1s[sl])),
                            "f1_std": float(np.std(f1s[sl])),
                            "acc_mean": float(np.mean(accs[sl])),
                            "final_loss_mean":
                                float(losses_np[sl, -1].mean()),
                            "final_loss_per_seed":
                                losses_np[sl, -1].tolist(),
                            # the whole multi-count batch trains
                            # together, so wall_s is SHARED across
                            # this group's cells and each cell's
                            # steps_per_sec is its own lanes' steps
                            # over that shared wall (cells sum to the
                            # batch throughput -- do not read a
                            # single padded cell's rate as a
                            # run_cell-style standalone measurement)
                            "wall_s": wall,
                            "steps_per_sec":
                                steps * s / max(wall, 1e-9),
                        }
                        if not none_only:
                            cell["fault"] = pl.spec
                            tel = lb.impl.telemetry(jax.tree.map(
                                lambda a: a[sl], sched_state))
                            cell["fault_telemetry"] = {
                                k: int(np.sum(v))
                                for k, v in tel.items()}
                        if not wire_none:
                            cell["transform"] = wp.spec
                            wtel = lb.impl.wire_telemetry(
                                jax.tree.map(lambda a: a[sl],
                                             sched_state))
                            cell["wire"] = {k: int(np.sum(v))
                                            for k, v in wtel.items()}
                        if not obs_none:
                            cell["obs"] = op.spec
                            # per-round series, leading seed axis
                            cell["obs_series"] = lb.impl.obs_series(
                                jax.tree.map(lambda a: a[sl],
                                             sched_state))
                        cells[ck] = cell
    out = {"cells": cells, "round_traces": traces, "lanes": n_lanes,
           "devices": n_dev, "wall_s": wall,
           "schedules": [sc.spec for sc in scheds],
           "cells_per_sec": len(cells) / max(wall, 1e-9),
           "steps_per_sec": steps * n_lanes / max(wall, 1e-9)}
    if not none_only:
        out["faults"] = [pl.spec for pl in plans]
    if not wire_none:
        out["transforms"] = [w.spec for w in wires]
    if not obs_none:
        out["obs"] = [o.spec for o in obss]
    return out


def run_grid(scfg: SweepConfig = SweepConfig(), shard=None):
    """Walk the full datasets x modes x client_counts grid -- one
    padded lane batch (ONE round compile, lanes sharded over devices)
    per (dataset, mode).  Returns {"cells": {key: cell}, "compare":
    {ds/n: {mode: f1_mean}}} where key = "dataset/mode/n_clients",
    exactly the pre-padding schema.

    ``scfg`` may also be a spec grid -- a sequence of
    ``repro.api.ExperimentSpec`` (e.g. from ``repro.api.spec_grid``)
    -- in which case the call is routed through ``repro.api.run_grid``
    (same schema, plus a per-cell ``spec_hash``).  ``shard`` defaults
    to the specs' shard policy on that route and to "auto" on the
    SweepConfig route; passing it explicitly overrides both."""
    if not isinstance(scfg, SweepConfig):
        from repro.api.session import run_grid as _api_run_grid
        return _api_run_grid(scfg, shard=shard)
    shard = "auto" if shard is None else shard
    cells, compare = {}, {}
    for ds, mode in itertools.product(scfg.datasets, scfg.modes):
        out = run_padded_cells(ds, mode, scfg, shard=shard)
        for nc, cell in out["cells"].items():
            cells[f"{ds}/{mode}/{nc}"] = cell
            compare.setdefault(f"{ds}/{nc}", {})[mode] = cell["f1_mean"]
    return {"cells": cells, "compare": compare}
