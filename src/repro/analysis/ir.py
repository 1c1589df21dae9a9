"""Shared IR-walking helpers: HLO-text shape/dtype parsing (consumed by
``repro.roofline``) and jaxpr traversal / abstract interpretation
(consumed by the taint, deadness, and retrace passes).

Two IR families live here because both sides of the repo read program
text rather than running it:

  * HLO text   -- the roofline model parses post-partitioning HLO for
    operand shapes and collective sizes.  ``DTYPE_BYTES`` / ``SHAPE_RE``
    / ``parse_shapes`` / ``shape_bytes`` / ``bytes_of`` are the single
    copies of the regex shape logic that used to be duplicated across
    ``roofline/analysis.py`` and ``roofline/hlo_costs.py``.
  * jaxprs     -- the static auditor traces the round function once
    with ``jax.make_jaxpr`` (no execution) and interprets the IR.
    ``sub_jaxprs`` / ``all_eqns`` walk the call hierarchy;
    :class:`AbstractInterpreter` is the forward dataflow engine the
    taint and deadness lattices plug into.

The interpreter folds constants as it goes: any equation whose inputs
are all concretely known (jaxpr constvars -- the Layout arrays, keys,
schedule scalars -- plus literals) is *executed* via the canonical
``primitive.bind`` interpreter loop, so downstream rules see concrete
``dynamic_slice`` offsets, permutations, and masks instead of opaque
tracers.  That is what makes per-client separation decidable on an
engine that stacks every client on one vmapped axis.
"""
from __future__ import annotations

import re

import numpy as np

from jax.extend import core as jcore
from jax.extend.source_info_util import summarize

# ----------------------------------------------------------------------
# HLO text helpers (single source of truth for the roofline parsers)
# ----------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

# e.g.  f32[8,128,3584]  -- dtype token + bracketed dims
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def parse_shapes(type_str: str):
    """All ``(dtype, dims_str)`` pairs in an HLO type string (handles
    tuple types: every bracketed shape in the string is returned)."""
    return [(dt, dims) for dt, dims in SHAPE_RE.findall(type_str)]


def shape_elems(dims: str) -> int:
    """Element count of a comma-joined dims string ('' = scalar = 1)."""
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def shape_bytes(dtype: str, dims: str) -> int:
    """Byte size of one ``dtype[dims]`` shape (unknown dtypes: 4B)."""
    return shape_elems(dims) * DTYPE_BYTES.get(dtype, 4)


def bytes_of(type_str: str) -> int:
    """Total byte size of every shape in an HLO type string."""
    return sum(shape_bytes(dt, dims) for dt, dims in parse_shapes(type_str))


# ----------------------------------------------------------------------
# jaxpr traversal
# ----------------------------------------------------------------------

# call-like primitives whose sub-jaxpr the interpreter INLINES (the
# equation is transparent: map invars -> sub-jaxpr args, run, map back).
# scan / while / cond have their own drivers; anything else (notably
# pallas_call) falls to the conservative default rule, which is sound.
INLINE_CALLS = ("jit", "closed_call", "core_call", "custom_jvp_call",
                "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
                "remat2", "checkpoint")


def closed(j):
    """Wrap an open Jaxpr as a ClosedJaxpr (no-op when already closed)."""
    if isinstance(j, jcore.ClosedJaxpr):
        return j
    return jcore.ClosedJaxpr(j, ())


def sub_jaxprs(eqn):
    """Yield every (ClosedJaxpr) nested in an equation's params --
    jit/scan ``jaxpr``, cond ``branches``, while ``cond_jaxpr`` /
    ``body_jaxpr``, custom_jvp ``call_jaxpr`` -- uniformly closed."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                yield closed(v)


def all_eqns(jaxpr):
    """Every equation in a (Closed)Jaxpr, recursively, as
    ``(path, eqn)`` with ``path`` a '/'-joined primitive-name trail."""
    j = jaxpr.jaxpr if isinstance(jaxpr, jcore.ClosedJaxpr) else jaxpr

    def walk(jx, path):
        for eqn in jx.eqns:
            yield path, eqn
            for sub in sub_jaxprs(eqn):
                yield from walk(sub.jaxpr, f"{path}/{eqn.primitive.name}"
                                if path else eqn.primitive.name)

    yield from walk(j, "")


def inline_jaxpr_of(eqn):
    """The single inlinable sub-jaxpr of a transparent call equation
    (jit's ``jaxpr``, custom_jvp's ``call_jaxpr``), or None."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        v = eqn.params.get(key)
        if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
            return closed(v)
    return None


def eqn_line(eqn, path=""):
    """One-line human rendering of an equation for reports: primitive,
    output avals, and the source location jax recorded at trace time."""
    outs = ", ".join(str(v.aval) for v in eqn.outvars)
    # "path/file.py:LINE:COL (fn)" of the user frame -> "[file.py:LINE]"
    where_src = summarize(eqn.source_info).split(" ", 1)[0]
    src = f"  [{':'.join(where_src.rsplit('/', 1)[-1].split(':')[:2])}]"
    where = f"{path}/" if path else ""
    return f"{where}{eqn.primitive.name} -> {outs}{src}"


def eval_eqn(eqn, in_vals):
    """Execute one equation concretely (the canonical interpreter-loop
    bind).  Returns the list of output values.  Callers guard with
    try/except: anything that refuses to fold is simply not concrete."""
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *in_vals, **bind_params)
    return list(out) if eqn.primitive.multiple_results else [out]


def as_np(v):
    """np.asarray when possible; extended-dtype values (typed PRNG
    keys) stay raw -- they still fold through ``eval_eqn``, and the
    lattices' ``from_concrete`` must tolerate them."""
    try:
        return np.asarray(v)
    except Exception:
        return v


# folding guard: never materialize giant intermediates while folding
_FOLD_ELEM_LIMIT = 4_000_000
# primitives never folded (executing them is the training loop / has
# no cheap eager path)
_NO_FOLD = {"scan", "while", "cond", "pallas_call", "custom_partitioning"}


class AbstractInterpreter:
    """Forward abstract interpretation over a ClosedJaxpr with constant
    folding and structured control flow.

    Subclasses define the lattice:

      top(aval)              unknown abstract value for an aval
      from_concrete(value)   abstract value of a known constant
      join(a, b, aval)       least upper bound (monotone!)
      equal(a, b)            lattice equality (fixpoint termination)
      rule(eqn, in_abs, in_conc) -> list of out abstract values, or
                             None to take the conservative default
      default(eqn, in_abs) -> out values when no rule applies
      on_eqn(path, eqn, in_abs, out_abs)   observation hook (tags)

    plus the scan plumbing ``enter_xs(a, aval)`` (abstract of one
    scanned slice from the stacked abstract) and ``stack_ys(a, aval)``
    (stacked abstract of the per-step ys).  The engine handles env
    management, literals, concrete folding, transparent call inlining,
    and fixpoints for scan/while (lattices must have finite height).
    """

    max_fixpoint_iters = 64

    def __init__(self):
        self.abs_env = {}        # Var -> abstract value
        self.conc_env = {}       # Var -> concrete np/jax value
        self.def_site = {}       # Var -> (path, eqn) that produced it
        self._path = ""

    # -- lattice interface (subclass) ----------------------------------
    def top(self, aval):
        raise NotImplementedError

    def bottom(self, aval):
        """Least element (the default rule folds inputs into it)."""
        raise NotImplementedError

    def from_concrete(self, value):
        raise NotImplementedError

    def join(self, a, b, aval):
        raise NotImplementedError

    def equal(self, a, b) -> bool:
        raise NotImplementedError

    def rule(self, eqn, in_abs, in_conc):
        return None

    def default(self, eqn, in_abs):
        out = self.bottom(eqn.outvars[0].aval)
        for a in in_abs:
            out = self.join(out, self._collapse_for_default(a),
                            eqn.outvars[0].aval)
        return [self._retop(out, ov.aval) for ov in eqn.outvars]

    def _collapse_for_default(self, a):
        return a

    def _retop(self, a, aval):
        return a

    def on_eqn(self, path, eqn, in_abs, out_abs):
        pass

    # -- env -----------------------------------------------------------
    def read_abs(self, var):
        if isinstance(var, jcore.Literal):
            return self.from_concrete(np.asarray(var.val))
        return self.abs_env[var]

    def read_conc(self, var):
        """Concrete value of a var, or None when unknown."""
        if isinstance(var, jcore.Literal):
            return np.asarray(var.val)
        return self.conc_env.get(var)

    def write(self, var, abs_val, conc_val=None, eqn=None):
        # dropped outputs (``_`` in the jaxpr) are written like any var:
        # no equation reads them, so their entries are never looked up
        self.abs_env[var] = abs_val
        if conc_val is not None:
            self.conc_env[var] = conc_val
        if eqn is not None:
            self.def_site[var] = (self._path, eqn)

    # -- driver --------------------------------------------------------
    def run(self, closed_jaxpr, in_abs, in_conc=None):
        """Interpret a ClosedJaxpr given abstract values (and optional
        concrete values, None-padded) for its invars.  Returns the
        output abstract values."""
        cj = closed(closed_jaxpr)
        jx = cj.jaxpr
        in_conc = in_conc or [None] * len(in_abs)
        for cv, const in zip(jx.constvars, cj.consts):
            cval = as_np(const)
            self.write(cv, self.from_concrete(cval), cval)
        for var, a, c in zip(jx.invars, in_abs, in_conc):
            self.write(var, a, c)
        self._run_eqns(jx)
        return [self.read_abs(v) for v in jx.outvars]

    def _run_eqns(self, jx):
        for eqn in jx.eqns:
            self._eqn(eqn)

    def _eqn(self, eqn):
        name = eqn.primitive.name
        in_abs = [self.read_abs(v) for v in eqn.invars]
        in_conc = [self.read_conc(v) for v in eqn.invars]

        # constant folding first: fully-known equations execute
        if (name not in _NO_FOLD and all(c is not None for c in in_conc)
                and all(np.prod(ov.aval.shape, dtype=np.int64)
                        <= _FOLD_ELEM_LIMIT for ov in eqn.outvars
                        if hasattr(ov.aval, "shape"))):
            try:
                outs = eval_eqn(eqn, in_conc)
            except Exception:
                outs = None
            if outs is not None:
                out_abs = []
                for ov, val in zip(eqn.outvars, outs):
                    cval = as_np(val)
                    a = self.from_concrete(cval)
                    self.write(ov, a, cval, eqn)
                    out_abs.append(a)
                self.on_eqn(self._path, eqn, in_abs, out_abs)
                return

        if name == "scan":
            out_abs = self._scan(eqn, in_abs, in_conc)
        elif name == "while":
            out_abs = self._while(eqn, in_abs, in_conc)
        elif name == "cond":
            out_abs = self._cond(eqn, in_abs, in_conc)
        elif name in INLINE_CALLS and inline_jaxpr_of(eqn) is not None:
            out_abs = self._inline(eqn, in_abs, in_conc)
        else:
            out_abs = self.rule(eqn, in_abs, in_conc)
            if out_abs is None:
                out_abs = self.default(eqn, in_abs)
        for ov, a in zip(eqn.outvars, out_abs):
            self.write(ov, a, None, eqn)
        self.on_eqn(self._path, eqn, in_abs, out_abs)

    def _nested(self, sub, eqn, in_abs, in_conc=None):
        """Run a sub-jaxpr in a child scope sharing the envs (vars are
        unique per trace, so sharing is safe) and the def-site map."""
        saved = self._path
        self._path = (f"{saved}/{eqn.primitive.name}" if saved
                      else eqn.primitive.name)
        try:
            return self.run(sub, in_abs, in_conc)
        finally:
            self._path = saved

    def _inline(self, eqn, in_abs, in_conc):
        sub = inline_jaxpr_of(eqn)
        n = len(sub.jaxpr.invars)
        # custom_jvp_call passes (primal args); jit passes all invars
        return self._nested(sub, eqn, in_abs[:n], in_conc[:n])[:len(
            eqn.outvars)]

    # scan plumbing (subclasses refine)
    def enter_xs(self, a, aval):
        return self._collapse_for_default(a)

    def stack_ys(self, a, aval):
        return self._retop(a, aval)

    def _scan(self, eqn, in_abs, in_conc):
        p = eqn.params
        nc, ncarry = p["num_consts"], p["num_carry"]
        body = closed(p["jaxpr"])
        consts = in_abs[:nc]
        # consts keep their concrete values inside the body (Layout
        # masks etc.); carry and xs slices are abstract-only
        consts_conc = list(in_conc[:nc])
        carry = list(in_abs[nc:nc + ncarry])
        xs = in_abs[nc + ncarry:]
        n_body_in = len(body.jaxpr.invars)
        xs_avals = [v.aval for v in
                    body.jaxpr.invars[nc + ncarry:n_body_in]]
        xs_slice = [self.enter_xs(a, av) for a, av in zip(xs, xs_avals)]
        carry_avals = [v.aval for v in body.jaxpr.invars[nc:nc + ncarry]]
        body_conc = consts_conc + [None] * (ncarry + len(xs_slice))
        ys_abs = None
        for _ in range(self.max_fixpoint_iters):
            outs = self._nested(body, eqn, consts + carry + xs_slice,
                                body_conc)
            new_carry = [self.join(c, o, av) for c, o, av in
                         zip(carry, outs[:ncarry], carry_avals)]
            ys_abs = outs[ncarry:]
            if all(self.equal(c, n) for c, n in zip(carry, new_carry)):
                carry = new_carry
                break
            carry = new_carry
        else:
            carry = [self.top(av) for av in carry_avals]
            outs = self._nested(body, eqn, consts + carry + xs_slice,
                                body_conc)
            ys_abs = outs[ncarry:]
        ys_avals = [v.aval for v in eqn.outvars[ncarry:]]
        return carry + [self.stack_ys(a, av)
                        for a, av in zip(ys_abs, ys_avals)]

    def _while(self, eqn, in_abs, in_conc):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body = closed(p["body_jaxpr"])
        cond = closed(p["cond_jaxpr"])
        cconsts = in_abs[:cn]
        bconsts = in_abs[cn:cn + bn]
        carry = list(in_abs[cn + bn:])
        ncarry = len(carry)
        cc = list(in_conc[:cn]) + [None] * ncarry
        bc = list(in_conc[cn:cn + bn]) + [None] * ncarry
        avals = [v.aval for v in eqn.outvars]
        for _ in range(self.max_fixpoint_iters):
            self._nested(cond, eqn, cconsts + carry, cc)
            outs = self._nested(body, eqn, bconsts + carry, bc)
            new_carry = [self.join(c, o, av) for c, o, av in
                         zip(carry, outs, avals)]
            if all(self.equal(c, n) for c, n in zip(carry, new_carry)):
                return new_carry
            carry = new_carry
        return [self.top(av) for av in avals]

    def _cond(self, eqn, in_abs, in_conc):
        branches = eqn.params["branches"]
        pred, ops = in_abs[0], in_abs[1:]
        avals = [v.aval for v in eqn.outvars]
        out = None
        for br in branches:
            bouts = self._nested(closed(br), eqn, list(ops),
                                 list(in_conc[1:]))
            if out is None:
                out = bouts
            else:
                out = [self.join(a, b, av) for a, b, av in
                       zip(out, bouts, avals)]
        # control-flow dependence on the predicate
        pc = self._collapse_for_default(pred)
        return [self.join(a, self._retop(pc, av), av)
                for a, av in zip(out, avals)]
