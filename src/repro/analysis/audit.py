"""Trace-and-audit orchestration: build a federation, trace its round
function ONCE with ``jax.make_jaxpr`` (no execution), and drive the
taint / deadness / retrace passes over the IR.

The harness closes over everything the passes treat as *known* -- the
round key, the labels, and the LayoutArrays -- so they arrive as jaxpr
constants the interpreters can fold (concrete masks, offsets, and
permutations are what keep the per-slot taint refinement alive), while
the carried state (params, optimizer state, schedule state) and the
feature matrix stay arguments so they can be seeded per client slot.

Seeding encodes the induction hypothesis "round inputs are already
separated": client slot i's params/opt/schedule leaves carry taint bit
i, feature column c carries the bit of the client that owns it, and
the audited theorem is that one round preserves that separation --
slot j's outputs carry only bit j plus declassified channel content.
A clean round therefore composes to a clean training run.

Tracing uses a deliberately tiny dataset slice (the jaxpr is
data-size-polymorphic in everything the passes check; a 2-batch scan
exercises the same equations as a 200-batch one) so the full
mode x schedule x first-layer grid audits in seconds.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax

from repro.analysis import deadness as DN
from repro.analysis import retrace as RT
from repro.analysis import taint as TA
from repro.analysis.barrier import audit_tracing
from repro.analysis.report import AnalysisReport, apply_waivers
from repro.core.protocol import (DeVertiFL, ProtocolConfig,
                                 make_round_fn, resolve_first_layer)

ALL_PASSES = ("taint", "deadness", "retrace")

# trace-size overrides: the audit proves structural contracts, which
# are invariant to dataset/batch size -- small sizes keep the grid fast
_TRACE_KW = dict(n_samples=32, batch_size=16, epochs=1, rounds=1)


def _as_pcfg(spec) -> ProtocolConfig:
    """Accept a ProtocolConfig or a repro.api ExperimentSpec."""
    if isinstance(spec, ProtocolConfig):
        return spec
    from repro.api.modes import get_mode          # lazy: api > analysis
    from repro.api.session import _protocol_config
    return _protocol_config(spec, get_mode(spec.mode).internal)


def combo_name(pcfg: ProtocolConfig) -> str:
    name = f"{pcfg.mode}/{pcfg.schedule}/{resolve_first_layer(pcfg)}"
    fault = getattr(pcfg, "fault", "none")
    if fault != "none":
        name = f"{name}/{fault}"
    transform = getattr(pcfg, "transform", "none")
    return name if transform == "none" else f"{name}/{transform}"


# ---------------------------------------------------------------------------
# the trace harness
# ---------------------------------------------------------------------------
class TracedRound:
    """One federation's round function as a ClosedJaxpr plus the
    leaf/aval bookkeeping the passes need."""

    def __init__(self, pcfg: ProtocolConfig):
        self.pcfg = pcfg
        self.combo = combo_name(pcfg)
        fed = DeVertiFL(pcfg)
        self.fed = fed
        self.n_train = len(fed.xtr)
        self.n_real = fed.layout.n_real
        self.n_padded = fed.layout.n_clients
        self.round_fn = make_round_fn(fed.model, fed.opt, pcfg,
                                      self.n_train, layout=fed.layout,
                                      sched_impl=fed._impl)
        _, params, opt_state, step0 = fed._init(
            jax.random.PRNGKey(pcfg.seed))
        sched_state = fed.init_sched_state()
        self.args = (params, opt_state, sched_state, fed._xtr)
        key = jax.random.fold_in(jax.random.PRNGKey(pcfg.seed), 1)
        ytr, lay = fed._ytr, fed._lay

        def harness(params, opt_state, sched_state, xtr):
            return self.round_fn(params, opt_state, step0, sched_state,
                                 key, xtr, ytr, lay)

        with audit_tracing():
            self.jaxpr, self.out_shape = jax.make_jaxpr(
                harness, return_shape=True)(*self.args)

    # -- leaf walks ----------------------------------------------------
    def _groups(self, tree):
        """Flatten a tuple-of-groups pytree into (group_idx, label,
        leaf) rows aligned with the jaxpr in/outvars."""
        rows = []
        for (path, leaf) in jax.tree_util.tree_flatten_with_path(
                tree)[0]:
            gi = path[0].idx
            rows.append((gi, jax.tree_util.keystr(path), leaf))
        return rows

    def _client_axis(self, shape) -> Optional[int]:
        """The stacked-client axis of a state leaf, by shape: params /
        opt leaves are [n, ...] (axis 0); schedule buffers are
        [n, B, W] (axis 0) or ring-stacked [depth, n, B, W] (axis
        ndim-3).  None for scalars / client-free leaves."""
        nd = len(shape)
        if nd >= 3 and shape[nd - 3] == self.n_padded:
            return nd - 3
        if nd >= 1 and shape[0] == self.n_padded:
            return 0
        return None

    def taint_seeds(self):
        """Input taints aligned with the jaxpr invars: state leaves
        per-slot on their client axis, features per-column by owner."""
        slot_bits = np.array([np.int64(1) << i
                              for i in range(self.n_padded)])
        in_abs = []
        for gi, label, leaf in self._groups(self.args):
            if gi == 3:       # xtr [n_train, F]: per-column ownership
                col = np.zeros(leaf.shape[1], np.int64)
                lo = self.fed.layout
                for i, (off, sz) in enumerate(zip(lo.offsets, lo.sizes)):
                    col[off:off + sz] |= np.int64(1) << i
                in_abs.append(TA.perslot(1, col))
                continue
            ax = self._client_axis(leaf.shape)
            if ax is None:
                in_abs.append(TA.EMPTY)
            else:
                in_abs.append(TA.perslot(ax, slot_bits))
        return in_abs

    def out_specs(self):
        """Per-outvar separation contract: carried state must stay
        per-slot on its client axis; the step counter and the scalar
        loss stream are aggregate telemetry, excluded by contract
        (docs/ARCHITECTURE.md section 8)."""
        specs = []
        names = ("params", "opt_state", "step_idx", "sched_state",
                 "losses")
        for gi, label, leaf in self._groups(self.out_shape):
            label = f"{names[gi]}{label[len(f'[{gi}]'):]}"
            if gi in (2, 4):
                specs.append(("skip", None, label))
                continue
            ax = self._client_axis(leaf.shape)
            if ax is None:
                specs.append(("skip", None, label))
            else:
                specs.append(("perslot", ax, label))
        return specs


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------
def audit(spec, passes: Optional[Sequence[str]] = None,
          lane_check: bool = True) -> AnalysisReport:
    """Statically audit one experiment's round function.

    ``spec`` is a repro.api ExperimentSpec or a ProtocolConfig; its
    training-size knobs are shrunk for tracing (the audited structure
    is size-polymorphic).  ``passes`` selects from
    ``("taint", "deadness", "retrace")`` (default: all).
    ``lane_check=False`` skips the retrace pass's lane-structural
    comparison (the expensive half; the CLI grid runs it once, not per
    combo).  Returns an :class:`AnalysisReport`; ``report.ok`` is the
    CI gate.
    """
    pcfg = _as_pcfg(spec).replace(**_TRACE_KW)
    passes = tuple(passes or ALL_PASSES)
    bad = set(passes) - set(ALL_PASSES)
    if bad:
        raise ValueError(f"unknown pass(es) {sorted(bad)}; "
                         f"choose from {ALL_PASSES}")
    report = AnalysisReport(combos=(combo_name(pcfg),),
                            passes_run=passes)
    tr = TracedRound(pcfg)

    if "taint" in passes:
        findings, channels = TA.run_taint(
            tr.jaxpr, tr.taint_seeds(), tr.out_specs(), tr.combo,
            tr.n_padded)
        report.findings.extend(findings)
        for ch, n in channels.items():
            report.channels[ch] = report.channels.get(ch, 0) + n

    if "deadness" in passes:
        # prove dead-slot zeros on a PADDED twin: an unpadded config
        # has no dead slots, so the proof obligation is the padded
        # variant every sweep lane actually runs
        if tr.n_real < tr.n_padded:
            twin = tr
        else:
            twin = TracedRound(
                pcfg.replace(max_clients=pcfg.n_clients + 1))
        in_abs = [np.ones(v.aval.shape, bool)
                  for v in twin.jaxpr.jaxpr.invars]
        report.findings.extend(DN.run_deadness(
            twin.jaxpr, in_abs, tr.combo, twin.n_real, twin.n_padded))

    if "retrace" in passes:
        report.findings.extend(RT.run_retrace(tr))
        if lane_check:
            report.findings.extend(RT.run_lane_check(pcfg.dataset))
        _stamp_traces(report)

    apply_waivers(report.findings)
    return report


def _stamp_traces(report: AnalysisReport):
    """static_round_traces == 1 iff the retrace pass ran and proved
    clean -- the static counterpart of the runtime ``round_traces``
    counter the sweep tests pin."""
    bad = any(f.pass_name == "retrace" and f.severity == "error"
              and not f.waived for f in report.findings)
    report.static_round_traces = 0 if bad else 1


def default_combos(modes=None, schedules=None, first_layers=None,
                   faults=None, transforms=None):
    """The registered mode x schedule x first-layer x fault x
    transform grid the CI lane audits: every federated mode (deduped
    through registry aliases), the shipped schedule families, the
    three built-in first-layer lanes ("auto" dedupes to its backend
    resolution), and -- for devertifl, the only mode faults and
    transforms inject into -- a composite fault plan exercising all
    three fault kinds plus the guard, and the hot wire transforms
    (repro.wire).  The fault and transform axes multiply schedules,
    not first layers (injection, guard and codec sit in the exchange,
    which is first-layer-agnostic), to keep the grid small; one
    combo per transform also chains the composite fault (the deepest
    engine chain: schedule -> fault -> wire)."""
    from repro.api.modes import MODES, get_mode
    if modes is None:
        seen = {}
        for name in MODES.names():
            m = get_mode(name)
            if m.kind == "federated" and m.internal not in seen:
                seen[m.internal] = m.internal
        modes = tuple(seen)
    if schedules is None:
        schedules = ("sync", "stale_k:2", "double_buffer",
                     "partial:0.5:det", "stale_k:1+partial:0.5")
    if first_layers is None:
        first_layers = ("masked", "slice", "pallas")
    if faults is None:
        faults = ("none", "crash:0.2:2+straggle:0.5:2+corrupt:0.05")
    if transforms is None:
        transforms = ("none", "int8+dp:0.1", "topk:0.5")
    combos = []
    for mode in modes:
        scheds = schedules if mode == "devertifl" else ("sync",)
        fts = faults if mode == "devertifl" else ("none",)
        wts = transforms if mode == "devertifl" else ("none",)
        fls, seen_fl = [], set()
        for fl in first_layers:
            r = resolve_first_layer(ProtocolConfig(mode=mode,
                                                   first_layer=fl))
            if r not in seen_fl:
                seen_fl.add(r)
                fls.append(fl)
        combos.extend((mode, sc, fl, "none", "none")
                      for sc in scheds for fl in fls)
        combos.extend((mode, sc, fls[0], ft, "none")
                      for ft in fts if ft != "none" for sc in scheds)
        combos.extend((mode, sc, fls[0], "none", t)
                      for t in wts if t != "none" for sc in scheds)
        combos.extend((mode, scheds[0], fls[0], ft, t)
                      for t in wts if t != "none"
                      for ft in fts if ft != "none")
    return combos


def audit_combos(modes=None, schedules=None, first_layers=None,
                 passes: Optional[Sequence[str]] = None,
                 dataset: str = "mnist", n_clients: int = 3,
                 lane_check: bool = True, faults=None,
                 transforms=None, progress=None) -> AnalysisReport:
    """Audit every registered mode x schedule x first-layer x fault x
    transform combination (the CI ``analysis`` lane).  The
    lane-structural retrace check runs ONCE for the grid (it compares
    sweep lane batches, which are per-dataset, not per-combo).
    Returns one merged report."""
    report = AnalysisReport()
    combos = default_combos(modes, schedules, first_layers, faults,
                            transforms)
    for i, (mode, sched, fl, fault, transform) in enumerate(combos):
        pcfg = ProtocolConfig(dataset=dataset, n_clients=n_clients,
                              mode=mode, schedule=sched, first_layer=fl,
                              fault=fault, transform=transform)
        if progress:
            progress(f"[{i + 1}/{len(combos)}] {combo_name(pcfg)}")
        report.merge(audit(pcfg, passes=passes, lane_check=False))
    if lane_check and "retrace" in (passes or ALL_PASSES):
        report.findings.extend(RT.run_lane_check(dataset))
        apply_waivers(report.findings)
    if "retrace" in (passes or ALL_PASSES):
        _stamp_traces(report)
    return report
