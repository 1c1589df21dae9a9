"""The padded-lane deadness prover (pass 2 of three).

PR 3 pinned, by runtime test, that padding the client axis to
``max_clients`` changes nothing: dead ``client_mask`` slots contribute
exact zeros to the exchange sum, the FedAvg weighting, and the loss
mean.  This pass upgrades the pin to a *static proof* over the traced
round jaxpr: a maybe-nonzero abstract interpretation (one bool per
element, ``True`` = possibly nonzero) in which the Layout's masks and
``client_mask`` are concrete constants, so every mask multiply kills
the dead slots *in the abstract domain* -- no execution, no sampling.

The engine marks each mask-weighted per-client term with a
``kind="term"`` barrier tag (see ``analysis/barrier.py``); the prover
checks the tagged value's dead slots (client-axis indices >=
``n_real``) are all-False.  The default transfer function is TOP
(all maybe-nonzero): zero-breaking ops like ``exp`` are automatically
conservative, and precision flows only through the zero-preserving
structure (mul / dot_general / shape ops) that the invariant actually
rides on.  The proof is structural: it assumes finite arithmetic
(0 * finite == 0); NaN/Inf garbage in dead parameter slots is excluded
by the padded init contract and out of scope here.
"""
from __future__ import annotations

import numpy as np

from repro.analysis import ir
from repro.analysis.barrier import TAG_PRIM_NAME
from repro.analysis.report import Finding

# f(0) == 0 holds elementwise: pattern passes through
_ZERO_PRESERVING_1 = {
    "neg", "abs", "sign", "sqrt", "cbrt", "square", "tanh", "sin",
    "tan", "asin", "atan", "sinh", "erf", "erf_inv", "log1p",
    "expm1", "stop_gradient", "copy", "convert_element_type",
    "reduce_precision", "real", "imag", "floor", "round",
}


def _shape(aval):
    return getattr(aval, "shape", ())


class DeadnessInterpreter(ir.AbstractInterpreter):
    """Maybe-nonzero propagation with dead-slot checks at term tags."""

    def __init__(self, n_real: int, n_padded: int, combo: str):
        super().__init__()
        self.n_real = int(n_real)
        self.n_padded = int(n_padded)
        self.combo = combo
        self.findings = []
        self.terms_checked = 0

    # lattice: np bool arrays, full shape
    def top(self, aval):
        return np.ones(_shape(aval), bool)

    def bottom(self, aval):
        return np.zeros(_shape(aval), bool)

    def from_concrete(self, value):
        v = ir.as_np(value)
        if not isinstance(v, np.ndarray) or v.dtype == object:
            return np.ones(getattr(v, "shape", ()), bool)
        with np.errstate(invalid="ignore"):
            return np.asarray(v != 0)

    def join(self, a, b, aval=None):
        return np.logical_or(a, b)

    def equal(self, a, b):
        return a.shape == b.shape and bool((a == b).all())

    def default(self, eqn, in_abs):
        return [self.top(ov.aval) for ov in eqn.outvars]

    def _collapse_for_default(self, a):
        return np.asarray(a.any())

    def _retop(self, a, aval):
        return np.broadcast_to(np.asarray(a).any(),
                               _shape(aval)).copy()

    def enter_xs(self, a, aval):
        out = a.any(axis=0) if a.ndim else a
        return np.broadcast_to(out, _shape(aval)).copy()

    def stack_ys(self, a, aval):
        return np.broadcast_to(a, _shape(aval)).copy()

    # ------------------------------------------------------------------
    def rule(self, eqn, in_abs, in_conc):
        name = eqn.primitive.name
        out_shape = _shape(eqn.outvars[0].aval)

        if name == TAG_PRIM_NAME:
            self._check_tag(eqn, in_abs[0])
            return [in_abs[0]]

        if name in _ZERO_PRESERVING_1:
            return [in_abs[0]]
        if name == "integer_pow":
            return [in_abs[0]] if eqn.params.get("y", 1) > 0 else None
        if name == "mul":
            return [np.logical_and(in_abs[0], in_abs[1])]
        if name == "div":
            return [in_abs[0].copy()]
        if name in ("add", "sub", "add_any", "max", "min", "rem",
                    "atan2", "nextafter"):
            return [np.logical_or(in_abs[0], in_abs[1])]
        if name == "select_n":
            out = np.zeros(out_shape, bool)
            for a in in_abs[1:]:
                out |= a
            return [out]
        if name == "clamp":
            return [in_abs[0] | in_abs[1] | in_abs[2]]
        if name in ("reduce_sum", "reduce_max", "reduce_min",
                    "reduce_prod", "reduce_or", "reduce_and"):
            axes = eqn.params["axes"]
            return [np.asarray(in_abs[0].any(axis=tuple(axes)))]
        if name == "broadcast_in_dim":
            bdims = eqn.params["broadcast_dimensions"]
            mid = [1] * len(out_shape)
            for i, d in enumerate(bdims):
                mid[d] = in_abs[0].shape[i]
            return [np.broadcast_to(in_abs[0].reshape(mid),
                                    out_shape).copy()]
        if name == "reshape":
            if eqn.params.get("dimensions") is not None:
                return None
            return [in_abs[0].reshape(out_shape)]
        if name == "transpose":
            return [np.transpose(in_abs[0],
                                 eqn.params["permutation"]).copy()]
        if name in ("squeeze", "expand_dims"):
            return [in_abs[0].reshape(out_shape)]
        if name == "rev":
            return [np.flip(in_abs[0],
                            eqn.params["dimensions"]).copy()]
        if name == "slice":
            sl = tuple(slice(s, l, (st if st else 1)) for s, l, st in
                       zip(eqn.params["start_indices"],
                           eqn.params["limit_indices"],
                           eqn.params.get("strides")
                           or [1] * len(out_shape)))
            return [in_abs[0][sl].copy()]
        if name == "concatenate":
            return [np.concatenate(in_abs,
                                   axis=eqn.params["dimension"])]
        if name == "split":
            cuts = np.cumsum(eqn.params["sizes"])[:-1]
            return [p.copy() for p in
                    np.split(in_abs[0], cuts, axis=eqn.params["axis"])]
        if name == "pad":
            return [self._pad(in_abs, eqn, out_shape)]
        if name == "dynamic_slice":
            return [self._dynamic_slice(in_abs, in_conc, eqn)]
        if name == "dynamic_update_slice":
            return [self._dynamic_update_slice(in_abs, in_conc, eqn)]
        if name == "dot_general":
            return [self._dot_general(in_abs, eqn)]
        if name == "gather":
            return self._via_bind(eqn, in_abs, in_conc)
        return None

    def _pad(self, in_abs, eqn, out_shape):
        a, padv = in_abs
        out = np.broadcast_to(np.asarray(padv).any(),
                              out_shape).copy()
        idx = []
        src = []
        for dim, (lo, hi, interior) in enumerate(
                eqn.params["padding_config"]):
            n = a.shape[dim]
            pos = lo + np.arange(n) * (interior + 1)
            keep = (pos >= 0) & (pos < out_shape[dim])
            idx.append(pos[keep])
            src.append(np.nonzero(keep)[0])
        out[np.ix_(*idx)] = a[np.ix_(*src)]
        return out

    def _dynamic_slice(self, in_abs, in_conc, eqn):
        a = in_abs[0]
        sizes = eqn.params["slice_sizes"]
        starts = in_conc[1:]
        if all(s is not None for s in starts):
            sl = tuple(
                slice(int(np.clip(int(s), 0, dim - sz)),
                      int(np.clip(int(s), 0, dim - sz)) + sz)
                for s, sz, dim in zip(starts, sizes, a.shape))
            return a[sl].copy()
        # unknown start: union over all windows per sliced axis
        out = a
        for k, sz in enumerate(sizes):
            if sz == a.shape[k]:
                continue
            windows = [np.take(out, range(s, s + sz), axis=k)
                       for s in range(a.shape[k] - sz + 1)]
            out = np.logical_or.reduce(windows)
        return out.copy()

    def _dynamic_update_slice(self, in_abs, in_conc, eqn):
        a, upd = in_abs[0], in_abs[1]
        starts = in_conc[2:]
        out = a.copy()
        if all(s is not None for s in starts):
            sl = tuple(
                slice(int(np.clip(int(s), 0, dim - usz)),
                      int(np.clip(int(s), 0, dim - usz)) + usz)
                for s, usz, dim in zip(starts, upd.shape, a.shape))
            out[sl] |= upd
            return out
        return np.logical_or(out, upd.any())

    def _dot_general(self, in_abs, eqn):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = in_abs[0], in_abs[1]
        letters = iter("abcdefghijklmnopqrstuvwxyz")
        l_sub = [None] * lhs.ndim
        r_sub = [None] * rhs.ndim
        for dl, dr in zip(lb, rb):
            c = next(letters)
            l_sub[dl] = r_sub[dr] = c
        for dl, dr in zip(lc, rc):
            c = next(letters)
            l_sub[dl] = r_sub[dr] = c
        for i in range(lhs.ndim):
            if l_sub[i] is None:
                l_sub[i] = next(letters)
        for i in range(rhs.ndim):
            if r_sub[i] is None:
                r_sub[i] = next(letters)
        out_sub = ([l_sub[d] for d in lb]
                   + [l_sub[d] for d in range(lhs.ndim)
                      if d not in lb and d not in lc]
                   + [r_sub[d] for d in range(rhs.ndim)
                      if d not in rb and d not in rc])
        spec = (f"{''.join(l_sub)},{''.join(r_sub)}"
                f"->{''.join(out_sub)}")
        counts = np.einsum(spec, lhs.astype(np.int64),
                           rhs.astype(np.int64))
        return counts > 0

    def _via_bind(self, eqn, in_abs, in_conc):
        """Execute the op on the bool pattern itself (int8-cast) when
        its non-pattern operands are concrete -- exact for gather."""
        if any(c is None for c in in_conc[1:]):
            return None
        try:
            vals = [in_abs[0].astype(np.int8)] + list(in_conc[1:])
            outs = ir.eval_eqn(eqn, vals)
            return [np.asarray(o) > 0 for o in outs]
        except Exception:
            return None

    # ------------------------------------------------------------------
    def _check_tag(self, eqn, pattern):
        if eqn.params["kind"] != "term":
            return
        ca = eqn.params.get("client_axis")
        if ca is None or ca >= pattern.ndim \
                or pattern.shape[ca] != self.n_padded:
            return
        self.terms_checked += 1
        if self.n_real >= self.n_padded:
            return
        dead = pattern.take(range(self.n_real, self.n_padded), axis=ca)
        if dead.any():
            bad = int(np.nonzero(dead.reshape(dead.shape[0], -1)
                                 .any(axis=1))[0][0]) + self.n_real
            path, e = self._path, eqn
            self.findings.append(Finding(
                "deadness", "unproven-dead-slot", self.combo,
                f"dead client slot {bad} of the tagged "
                f"{eqn.params['channel']!r} term is not provably zero",
                chain=(ir.eqn_line(e, path),)))


def run_deadness(closed_jaxpr, in_abs, combo, n_real, n_padded):
    """Prove dead-slot zeros over a traced round.  Returns findings."""
    interp = DeadnessInterpreter(n_real, n_padded, combo)
    interp.run(closed_jaxpr, in_abs)
    findings = list(interp.findings)
    if interp.terms_checked == 0:
        findings.append(Finding(
            "deadness", "no-terms-observed", combo,
            "no mask-weighted term tags were observed in the traced "
            "round; deadness instrumentation is not wired into this "
            "path", severity="warning"))
    return findings
