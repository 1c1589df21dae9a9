"""The privacy-flow taint audit (pass 1 of three).

Lattice.  A :class:`Taint` over-approximates which clients' RAW
features may have influenced each array:

  * ``Taint(None, bits)``   -- *uniform*: every element may carry the
    client sources in the ``bits`` bitmask (bit i = client i).
  * ``Taint(axis, bits[])`` -- *per-slot*: along one distinguished
    axis (the stacked client axis, or the canonical feature-column
    axis), slot s carries only ``bits[s]``.

Per-slot structure is what makes the audit decidable on this engine:
every client lives on one vmapped axis of the same stacked arrays, so
a taint domain without an axis-indexed refinement would collapse to
"everything touches everything" at the first stack.  Three mechanisms
keep the refinement alive through a real round trace:

  1. constant folding (ir.AbstractInterpreter): Layout offsets, masks,
     permutations, and PRNG keys are jaxpr constants, so
     ``dynamic_slice`` starts and gather indices are concrete;
  2. structural rules: dot_general preserves batch dims, slice/pad/
     concat/dynamic_update_slice move bits between slots explicitly;
  3. zero-pattern refinement: multiplying a uniform-per-column taint by
     a concrete block-diagonal client mask yields a PER-SLOT taint --
     the masked first layer's ``xb[None] * masks[:, None, :]`` is
     exactly this shape.

Declassification.  The engine marks its declared channels with the
:mod:`repro.analysis.barrier` tag primitive; a ``kind="declass"`` tag
clears client-source bits (the hidden-output exchange and the FedAvg
mean ARE the protocol -- the audit's theorem is that nothing else
crosses).  The audited contract per round output: client slot j's
parameters, optimizer state, and schedule state may carry only bit j
(its own raw features) plus declassified content.  One round suffices
by induction: inputs are seeded per-slot, so a clean round composes.

On violation the pass reports the offending equation chain, walked
backward through recorded def-sites following the leaking bit.
"""
from __future__ import annotations

import numpy as np

from jax.extend import core as jcore

from repro.analysis import ir
from repro.analysis.barrier import TAG_PRIM_NAME
from repro.analysis.report import Finding


class Taint:
    """Client-source bitmask, uniform or refined along one axis."""
    __slots__ = ("axis", "bits")

    def __init__(self, axis, bits):
        self.axis = axis
        self.bits = bits if axis is None else np.asarray(bits, np.int64)

    def __repr__(self):
        if self.axis is None:
            return f"Taint({self.bits:#x})"
        return f"Taint(axis={self.axis}, bits={self.bits.tolist()})"


EMPTY = Taint(None, 0)


def uniform(bits: int) -> Taint:
    return EMPTY if bits == 0 else Taint(None, int(bits))


def perslot(axis: int, bits) -> Taint:
    return Taint(int(axis), bits)


def collapse(t: Taint) -> int:
    if t.axis is None:
        return t.bits
    return int(np.bitwise_or.reduce(t.bits)) if t.bits.size else 0


def is_empty(t: Taint) -> bool:
    return collapse(t) == 0


def is_mixed(t) -> bool:
    """True when some element carries MORE than one client bit -- the
    signature of cross-client mixing.  Per-slot taints with one owner
    bit per slot (a clean per-client stack, or per-column feature
    ownership) are not mixed."""
    if t is None or is_empty(t):
        return False
    bits = np.ravel(t.bits) if t.axis is not None else [t.bits]
    return any(int(b) & (int(b) - 1) for b in bits)


def _or_into(bits_arr, extra: int):
    return bits_arr if extra == 0 else bits_arr | np.int64(extra)


def join(a: Taint, b: Taint) -> Taint:
    if a.axis is None and b.axis is None:
        return uniform(a.bits | b.bits)
    if a.axis is None:
        return perslot(b.axis, _or_into(b.bits, a.bits))
    if b.axis is None:
        return perslot(a.axis, _or_into(a.bits, b.bits))
    if a.axis == b.axis and a.bits.shape == b.bits.shape:
        return perslot(a.axis, a.bits | b.bits)
    return uniform(collapse(a) | collapse(b))


# single-operand, shape-preserving: taint passes through untouched
_PASSTHROUGH = {
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "sin", "cos",
    "tan", "asin", "acos", "atan", "sinh", "cosh", "erf", "erfc",
    "erf_inv", "neg", "sign", "floor", "ceil", "round", "abs", "sqrt",
    "rsqrt", "cbrt", "square", "integer_pow", "not", "is_finite",
    "convert_element_type", "stop_gradient", "copy", "real", "imag",
    "conj", "reduce_precision", "population_count", "clz",
    "logistic", "exp2",
}

# n-ary elementwise (equal shapes in jaxpr IR; scalars pre-broadcast)
_ELEMENTWISE_N = {
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2",
    "and", "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "nextafter", "add_any", "select_n", "clamp", "igamma", "igammac",
    "complex",
}

_REDUCES = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
            "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin"}


class TaintInterpreter(ir.AbstractInterpreter):
    """Forward taint propagation with def-site provenance."""

    def __init__(self, n_slots_hint=0):
        super().__init__()
        self.all_bits = (1 << max(n_slots_hint, 1)) - 1
        self.channels = {}        # channel name -> tag count
        self.blame = {}           # var -> (path, eqn) that introduced
        #                           multi-client mixing in its lineage

    # lattice
    def top(self, aval):
        return uniform(self.all_bits)

    def bottom(self, aval):
        return EMPTY

    def from_concrete(self, value):
        return EMPTY

    def join(self, a, b, aval=None):
        return join(a, b)

    def equal(self, a, b):
        if a.axis is None and b.axis is None:
            return a.bits == b.bits
        if a.axis is None or b.axis is None:
            return False
        return (a.axis == b.axis and a.bits.shape == b.bits.shape
                and bool((a.bits == b.bits).all()))

    def _collapse_for_default(self, a):
        return uniform(collapse(a))

    # scan xs: one slice along the leading axis
    def enter_xs(self, a, aval):
        if a.axis is None:
            return a
        if a.axis == 0:
            return uniform(collapse(a))
        return perslot(a.axis - 1, a.bits)

    def stack_ys(self, a, aval):
        if a.axis is None:
            return a
        return perslot(a.axis + 1, a.bits)

    # ------------------------------------------------------------------
    def on_eqn(self, path, eqn, in_abs, out_abs):
        """Blame bookkeeping: remember, per var, the equation where
        multi-client mixing first entered its lineage.  An output that
        is mixed while no input was inherits nothing -- that equation
        IS the mixing point."""
        src, mixed_in = None, False
        for iv, a in zip(eqn.invars, in_abs):
            if isinstance(iv, jcore.Literal) or not is_mixed(a):
                continue
            mixed_in = True
            b = self.blame.get(iv)
            if b is not None:
                src = b
                break
        if src is None and not mixed_in:
            src = (path, eqn)
        if src is None:
            return
        for ov, a in zip(eqn.outvars, out_abs):
            if is_mixed(a):
                self.blame[ov] = src

    def rule(self, eqn, in_abs, in_conc):
        name = eqn.primitive.name
        out_aval = eqn.outvars[0].aval

        if name == TAG_PRIM_NAME:
            kind = eqn.params["kind"]
            ch = eqn.params["channel"]
            self.channels[ch] = self.channels.get(ch, 0) + 1
            if kind == "declass":
                return [EMPTY]
            return [in_abs[0]]

        if name in _PASSTHROUGH:
            return [in_abs[0]]

        if name in _ELEMENTWISE_N:
            if name == "mul":
                ref = self._mul_refine(in_abs, in_conc, out_aval)
                if ref is not None:
                    return [ref]
            out = EMPTY
            out_shape = getattr(out_aval, "shape", ())
            for a, v in zip(in_abs, eqn.invars):
                shape = getattr(v.aval, "shape", ())
                if a.axis is not None and shape != out_shape:
                    # numpy-style broadcast: axes right-align, so the
                    # slot axis survives iff its extent is unchanged
                    off = len(out_shape) - len(shape)
                    ax = a.axis + off
                    if (off >= 0 and 0 <= ax < len(out_shape)
                            and shape[a.axis] == out_shape[ax]):
                        a = a if ax == a.axis else perslot(ax, a.bits)
                    else:
                        a = uniform(collapse(a))
                out = join(out, a)
            return [out] * len(eqn.outvars)

        if name in _REDUCES:
            o = self._reduce_axes(in_abs[0] if in_abs else EMPTY,
                                  eqn.params.get("axes", ()))
            return [o] * len(eqn.outvars)

        if name == "broadcast_in_dim":
            return [self._broadcast(in_abs[0], eqn)]
        if name == "reshape":
            return [self._reshape(in_abs[0], eqn)]
        if name == "transpose":
            return [self._transpose(in_abs[0], eqn)]
        if name == "squeeze":
            return [self._squeeze(in_abs[0], eqn)]
        if name == "expand_dims":
            return [self._expand_dims(in_abs[0], eqn)]
        if name == "slice":
            return [self._slice(in_abs[0], eqn)]
        if name == "dynamic_slice":
            return [self._dynamic_slice(in_abs, in_conc, eqn)]
        if name == "dynamic_update_slice":
            return [self._dynamic_update_slice(in_abs, in_conc, eqn)]
        if name == "pad":
            return [self._pad(in_abs, eqn)]
        if name == "concatenate":
            return [self._concatenate(in_abs, eqn)]
        if name == "split":
            return self._split(in_abs[0], eqn)
        if name == "dot_general":
            return [self._dot_general(in_abs, eqn)]
        if name == "gather":
            return [self._gather(in_abs, in_conc, eqn)]
        if name in ("scatter-add", "scatter", "scatter-mul",
                    "scatter-min", "scatter-max", "scatter_add"):
            extra = collapse(in_abs[1]) | collapse(in_abs[2])
            return [join(in_abs[0], uniform(extra))]
        if name in ("rev",):
            a = in_abs[0]
            if a.axis is not None and a.axis in eqn.params["dimensions"]:
                return [perslot(a.axis, a.bits[::-1].copy())]
            return [a]
        if name == "iota":
            return [EMPTY]
        return None

    # -- structural rules ----------------------------------------------
    def _mul_refine(self, in_abs, in_conc, out_aval):
        """mul by a concrete mask: zero entries of the mask erase taint
        positionally, and may REFINE a taint onto a different axis --
        e.g. per-column(features) x block-diagonal client masks
        [n, 1, F] -> per-slot(clients)."""
        for (a, c) in ((in_abs[0], in_conc[1]), (in_abs[1], in_conc[0])):
            if c is None or is_empty(a):
                continue
            try:
                nz = np.broadcast_to(np.asarray(c) != 0, out_aval.shape)
            except Exception:
                continue
            ndim = len(out_aval.shape)
            if a.axis is None:
                if not nz.any():
                    return EMPTY
                return None     # uniform stays uniform
            k = a.axis
            if k >= ndim:
                return None
            # candidate result axes: keep k, or re-slot onto any axis
            best = None
            for cand in range(ndim):
                red = tuple(d for d in range(ndim) if d not in (cand, k))
                nz2 = nz.any(axis=red) if red else nz
                if cand == k:
                    nz2 = np.diag(nz2) if nz2.ndim == 2 else nz2
                    bits = np.where(nz2, a.bits[:nz2.shape[0]], 0)
                    t = perslot(k, bits.astype(np.int64))
                else:
                    if cand < k:
                        m = nz2          # [cand_dim, k_dim]
                    else:
                        m = nz2.T        # transpose to [cand_dim, k_dim]
                    bits = np.zeros(m.shape[0], np.int64)
                    for s in range(m.shape[0]):
                        sel = a.bits[np.nonzero(m[s])[0]]
                        bits[s] = (np.bitwise_or.reduce(sel)
                                   if sel.size else 0)
                    t = perslot(cand, bits)
                score = self._precision(t)
                if best is None or score < best[0]:
                    best = (score, t)
            return best[1] if best else None
        return None

    @staticmethod
    def _precision(t):
        """Lower = more precise: max popcount across slots."""
        if t.axis is None:
            return bin(t.bits).count("1") + 1000
        return max((bin(int(b)).count("1") for b in t.bits), default=0)

    def _reduce_axes(self, a, axes):
        if a.axis is None:
            return a
        if a.axis in axes:
            return uniform(collapse(a))
        return perslot(a.axis - sum(1 for x in axes if x < a.axis),
                       a.bits)

    def _broadcast(self, a, eqn):
        if a.axis is None:
            return a
        bdims = eqn.params["broadcast_dimensions"]
        if a.axis >= len(bdims):
            return uniform(collapse(a))
        out_axis = bdims[a.axis]
        out_dim = eqn.params["shape"][out_axis]
        bits = a.bits
        if bits.shape[0] != out_dim:    # size-1 dim expanded
            bits = np.repeat(bits[:1], out_dim)
        return perslot(out_axis, bits)

    def _reshape(self, a, eqn):
        if a.axis is None:
            return a
        if eqn.params.get("dimensions") is not None:
            return uniform(collapse(a))
        old = eqn.invars[0].aval.shape
        new = tuple(eqn.params["new_sizes"])
        k = a.axis
        pre = int(np.prod(old[:k], dtype=np.int64))
        post = int(np.prod(old[k + 1:], dtype=np.int64))
        run = 1
        for j, d in enumerate(new):
            if (run == pre and d == old[k]
                    and int(np.prod(new[j + 1:], dtype=np.int64)) == post):
                return perslot(j, a.bits)
            run *= d
        return uniform(collapse(a))

    def _transpose(self, a, eqn):
        if a.axis is None:
            return a
        perm = eqn.params["permutation"]
        return perslot(list(perm).index(a.axis), a.bits)

    def _squeeze(self, a, eqn):
        if a.axis is None:
            return a
        dims = eqn.params["dimensions"]
        if a.axis in dims:
            return uniform(collapse(a))
        return perslot(a.axis - sum(1 for d in dims if d < a.axis),
                       a.bits)

    def _expand_dims(self, a, eqn):
        if a.axis is None:
            return a
        dims = eqn.params["dimensions"]
        return perslot(a.axis + sum(1 for d in dims if d <= a.axis),
                       a.bits)

    def _slice(self, a, eqn):
        if a.axis is None:
            return a
        k = a.axis
        start = eqn.params["start_indices"][k]
        limit = eqn.params["limit_indices"][k]
        strides = eqn.params.get("strides")
        step = strides[k] if strides else 1
        return perslot(k, a.bits[start:limit:step].copy())

    def _split(self, a, eqn):
        sizes = eqn.params["sizes"]
        if a.axis is None or a.axis != eqn.params["axis"]:
            return [a] * len(sizes)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        return [perslot(a.axis, a.bits[s:s + n].copy())
                for s, n in zip(starts, sizes)]

    def _dynamic_slice(self, in_abs, in_conc, eqn):
        a = in_abs[0]
        if a.axis is None:
            return a
        k = a.axis
        sizes = eqn.params["slice_sizes"]
        shape = eqn.invars[0].aval.shape
        start_c = in_conc[1 + k]
        if sizes[k] == shape[k]:
            return perslot(k, a.bits)
        if start_c is not None:
            s = int(np.clip(int(start_c), 0, shape[k] - sizes[k]))
            return perslot(k, a.bits[s:s + sizes[k]].copy())
        return uniform(collapse(a))

    def _dynamic_update_slice(self, in_abs, in_conc, eqn):
        x, upd = in_abs[0], in_abs[1]
        shape = eqn.outvars[0].aval.shape
        k = x.axis if x.axis is not None else (
            upd.axis if upd.axis is not None else None)
        if k is None:
            return join(x, upd)
        base = (x.bits.copy() if x.axis == k
                else np.full(shape[k], collapse(x), np.int64))
        u_shape = eqn.invars[1].aval.shape
        start_c = in_conc[2 + k]
        ubits = (upd.bits if upd.axis == k
                 else np.full(u_shape[k], collapse(upd), np.int64))
        if start_c is not None:
            s = int(np.clip(int(start_c), 0, shape[k] - u_shape[k]))
            base[s:s + u_shape[k]] |= ubits
        else:
            base |= np.int64(collapse(upd))
        return perslot(k, base)

    def _pad(self, in_abs, eqn):
        a, padv = in_abs[0], in_abs[1]
        cfg = eqn.params["padding_config"]
        out_shape = eqn.outvars[0].aval.shape
        in_shape = eqn.invars[0].aval.shape
        pb = np.int64(collapse(padv))

        def along(k, bits_at):
            lo, hi, interior = cfg[k]
            bits = np.full(out_shape[k], pb, np.int64)
            for i in range(in_shape[k]):
                pos = lo + i * (interior + 1)
                if 0 <= pos < out_shape[k]:
                    bits[pos] |= np.int64(bits_at(i))
            return perslot(k, bits)

        # pad is the transpose of ``slice``: it places one client's
        # cotangent chunk back into the stacked buffer, so the padded
        # axis is where slot structure is created -- the pad region
        # carries only the pad value's taint, never the operand's.
        padded = [k for k, c in enumerate(cfg)
                  if tuple(c) != (0, 0, 0)]
        if a.axis is not None and a.axis in padded:
            return along(a.axis, lambda i: a.bits[i])
        if a.axis is not None:
            # per-slot on an untouched axis: either keep that view or
            # re-slot onto the padded axis; choose the more precise.
            keep = perslot(a.axis, a.bits | pb)
            if not padded or collapse(a) == 0:
                return keep
            u = collapse(a)
            cand = along(padded[0], lambda i: u)
            return (cand if self._precision(cand)
                    <= self._precision(keep) else keep)
        if not padded or collapse(a) == 0:
            return join(a, uniform(pb))
        u = collapse(a)
        return along(padded[0], lambda i: u)

    def _concatenate(self, in_abs, eqn):
        dim = eqn.params["dimension"]
        shapes = [v.aval.shape for v in eqn.invars]
        axes = {a.axis for a in in_abs if a.axis is not None}
        if axes <= {dim}:
            # covers the all-uniform case too: stacking per-client
            # tensors (stack = broadcast + concat) yields per-slot
            # taint along the new axis, one operand's bits per span
            segs = []
            for a, sh in zip(in_abs, shapes):
                if a.axis == dim:
                    segs.append(a.bits)
                else:
                    segs.append(np.full(sh[dim], collapse(a), np.int64))
            return perslot(dim, np.concatenate(segs))
        if len(axes) == 1:
            ax = axes.pop()
            if ax != dim and all(sh[ax] == shapes[0][ax]
                                 for sh in shapes):
                bits = np.zeros(shapes[0][ax], np.int64)
                for a in in_abs:
                    if a.axis == ax:
                        bits |= a.bits
                    else:
                        bits |= np.int64(collapse(a))
                return perslot(ax, bits)
        return uniform(int(np.bitwise_or.reduce(
            [np.int64(collapse(a)) for a in in_abs])))

    def _dot_general(self, in_abs, eqn):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs_aval, rhs_aval = (v.aval for v in eqn.invars[:2])
        lhs_free = [d for d in range(len(lhs_aval.shape))
                    if d not in lc and d not in lb]
        rhs_free = [d for d in range(len(rhs_aval.shape))
                    if d not in rc and d not in rb]

        def side(a, contract, batch, free, offset):
            if a.axis is None:
                return a
            k = a.axis
            if k in batch:
                return perslot(list(batch).index(k), a.bits)
            if k in contract:
                return uniform(collapse(a))
            return perslot(len(batch) + offset + free.index(k), a.bits)

        lt = side(in_abs[0], lc, lb, lhs_free, 0)
        rt = side(in_abs[1], rc, rb, rhs_free, len(lhs_free))
        return join(lt, rt)

    def _gather(self, in_abs, in_conc, eqn):
        a, idx_t = in_abs[0], in_abs[1]
        dn = eqn.params["dimension_numbers"]
        sizes = eqn.params["slice_sizes"]
        shape = eqn.invars[0].aval.shape
        extra = uniform(collapse(idx_t))
        if a.axis is None:
            return join(a, extra)
        k = a.axis
        collapsed = set(dn.collapsed_slice_dims)
        batching = set(getattr(dn, "operand_batching_dims", ()) or ())
        if sizes[k] == shape[k] and k not in collapsed \
                and k not in batching:
            kept = [d for d in range(len(shape))
                    if d not in collapsed and d not in batching]
            out_axis = dn.offset_dims[kept.index(k)]
            return join(perslot(out_axis, a.bits), extra)
        exact = self._gather_exact(a, in_conc, eqn, k, shape)
        if exact is not None:
            return join(exact, extra)
        return join(uniform(collapse(a)), extra)

    def _gather_exact(self, a, in_conc, eqn, k, shape):
        """Concrete-index gathers (``w[i]``, column takes) tracked
        exactly: gather an array of source-slot ids through the same
        equation, then read off which slots feed each output span."""
        if in_conc[1] is None:
            return None
        out_shape = eqn.outvars[0].aval.shape
        if (int(np.prod(shape, dtype=np.int64)) > 4_000_000
                or int(np.prod(out_shape, dtype=np.int64)) > 4_000_000):
            return None
        mid = [1] * len(shape)
        mid[k] = shape[k]
        ids = np.broadcast_to(
            np.arange(shape[k], dtype=np.int32).reshape(mid),
            shape)
        try:
            out_ids = np.asarray(
                ir.eval_eqn(eqn, [ids, in_conc[1]])[0])
        except Exception:
            return None
        if out_ids.ndim == 0:
            return uniform(int(a.bits[int(out_ids)]))
        best = None
        for cand in range(out_ids.ndim):
            bits = np.zeros(out_ids.shape[cand], np.int64)
            for s in range(out_ids.shape[cand]):
                uniq = np.unique(np.take(out_ids, s, axis=cand))
                bits[s] = np.bitwise_or.reduce(a.bits[uniq]) \
                    if uniq.size else 0
            t = perslot(cand, bits)
            score = self._precision(t)
            if best is None or score < best[0]:
                best = (score, t)
        return best[1] if best else None

    # -- provenance -----------------------------------------------------
    def _descend(self, v, eqn):
        """Hop from an outer outvar of a structured eqn (scan / while /
        cond / inlined call) to the aligned outvar of its sub-jaxpr.
        Def-sites are shared across scopes, so the walk continues
        inside the body where the offending equation actually lives."""
        name = eqn.primitive.name
        p = eqn.params
        if name == "scan":
            sub = p["jaxpr"]
        elif name == "while":
            sub = p["body_jaxpr"]
        elif name == "cond":
            sub = p["branches"][0]
        else:
            sub = ir.inline_jaxpr_of(eqn)
        if sub is None:
            return None
        jx = ir.closed(sub).jaxpr
        try:
            idx = eqn.outvars.index(v)
        except ValueError:
            return None
        # scan outvars = carry + ys and body outvars = carry + ys;
        # while/cond/call outvars align 1:1 -- same index either way
        if idx >= len(jx.outvars):
            return None
        inner = jx.outvars[idx]
        if isinstance(inner, jcore.Literal):
            return None
        return inner

    def explain(self, var, bit: int, limit=64):
        """Equation chain from ``var`` back toward the source of one
        leaking client bit (most recent def-sites, violating bit
        followed greedily, descending into scan/while/cond bodies)."""
        lines, seen, v = [], set(), var
        blame = None
        while v in self.def_site and v not in seen and \
                len(lines) < limit:
            seen.add(v)
            blame = self.blame.get(v, blame)
            path, eqn = self.def_site[v]
            lines.append(ir.eqn_line(eqn, path))
            nxt = self._descend(v, eqn)
            if nxt is not None:
                t = self.abs_env.get(nxt)
                if t is None or not (collapse(t) & bit) or \
                        nxt in seen:
                    nxt = None
            if nxt is None:
                fallback = None
                for iv in eqn.invars:
                    if isinstance(iv, jcore.Literal):
                        continue
                    t = self.abs_env.get(iv)
                    if t is None or not (collapse(t) & bit) or \
                            iv in seen:
                        continue
                    # prefer an operand the walk can keep following
                    # over a dead end (e.g. a loop-carry invar)
                    if iv in self.def_site:
                        nxt = iv
                        break
                    fallback = fallback or iv
                nxt = nxt or fallback
            if nxt is None:
                break
            v = nxt
        blame = self.blame.get(v, blame)
        if blame is not None:
            bpath, beqn = blame
            lines.append("<- mixing introduced at "
                         + ir.eqn_line(beqn, bpath))
        lines.append(f"<- carries client bit {bit:#x} "
                     "from a tainted source input")
        return lines


def check_round_outputs(interp, closed_jaxpr, out_abs, out_specs,
                        combo):
    """Verify per-slot separation on the round outputs.

    ``out_specs`` aligns with the jaxpr outvars: each entry is
    ``("perslot", client_axis, label)`` -- slot j may carry only bit
    j -- or ``("skip", None, label)`` for aggregate telemetry (the
    scalar loss stream, excluded by contract)."""
    findings = []
    outvars = closed_jaxpr.jaxpr.outvars
    for var, t, (check, axis, label) in zip(outvars, out_abs,
                                            out_specs):
        if check == "skip":
            continue
        if is_empty(t):
            continue
        if t.axis == axis:
            bad = [(s, int(b) & ~(1 << s))
                   for s, b in enumerate(t.bits)
                   if int(b) & ~(1 << s)]
            if not bad:
                continue
            s, leaked = bad[0]
            bit = leaked & -leaked
            findings.append(Finding(
                "taint", "cross-client-flow", combo,
                f"{label}: client slot {s} carries foreign client "
                f"bit(s) {leaked:#x} outside declared channels",
                chain=tuple(interp.explain(var, bit))))
        else:
            bits = collapse(t)
            bit = bits & -bits
            findings.append(Finding(
                "taint", "unseparable-flow", combo,
                f"{label}: taint could not be separated per client "
                f"slot (carries {bits:#x} uniformly; expected "
                f"per-slot on axis {axis})",
                chain=tuple(interp.explain(var, bit))))
    return findings


def run_taint(closed_jaxpr, in_abs, out_specs, combo, n_slots):
    """Drive the taint interpreter over a traced round and check the
    per-slot separation contract.  Returns (findings, channels)."""
    interp = TaintInterpreter(n_slots_hint=n_slots)
    out_abs = interp.run(closed_jaxpr, in_abs)
    findings = check_round_outputs(interp, ir.closed(closed_jaxpr),
                                   out_abs, out_specs, combo)
    if not interp.channels:
        findings.append(Finding(
            "taint", "no-channels-observed", combo,
            "no declared-channel tags were observed in the traced "
            "round; the audit instrumentation is not wired into this "
            "path", severity="warning"))
    return findings, interp.channels
