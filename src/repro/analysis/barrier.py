"""The audit barrier: an identity primitive that marks declared
cross-client channels (and maskable terms) in the traced jaxpr.

De-VertiFL's privacy claim is *relational*: client i's raw features may
reach client j only through the declared first-layer hidden-output
exchange (and the FedAvg parameter mean).  A dataflow auditor therefore
needs the declared channels to be visible IN the IR.  This module
provides :func:`tag` -- an identity function that the engine calls at
exactly those reductions (``core/exchange.py``,
``core/protocol.py``, ``schedule/engine.py``):

  tag(x, "declass", "exchange")   the masked hidden-output sum every
                                  client consumes (the paper's channel)
  tag(x, "declass", "fedavg")     the masked parameter mean
  tag(x, "term", channel, client_axis=0)
                                  a mask-weighted per-client term whose
                                  dead padded slots the deadness pass
                                  must prove structurally zero

Outside an :func:`audit_tracing` context ``tag`` returns its argument
untouched -- zero equations, zero overhead, so production traces (and
the ``round_traces == 1`` compile-once contract) are bit-identical to
a build without the auditor.  Inside the context it binds ``tag_p``, an
identity primitive registered as linear (its transpose re-tags the
cotangent: the transpose of the declared forward exchange is precisely
the declared backward exchange of the verticomb baseline) and
vectorized under vmap, so it survives ``jax.grad`` / ``jax.vmap``
tracing and lands in the jaxpr where the passes can see it.

The context is thread-local and must only wrap ``jax.make_jaxpr``
calls, never jitted *executions*: a cached compiled function traced
under the context would carry tag equations for its lifetime (they
lower to identity, so even that is harmless -- just wasteful).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

TAG_PRIM_NAME = "repro_audit_tag"

tag_p = Primitive(TAG_PRIM_NAME)
tag_p.def_impl(lambda x, **_: x)
tag_p.def_abstract_eval(lambda aval, **_: aval)
# linear: jvp passes tangents through, and the transpose of a DECLARED
# CHANNEL re-tags the cotangent -- backward flows through the exchange
# stay declared (that is verticomb's backward exchange).  A "term" tag
# does NOT transpose to a term: the cotangent of a mask-weighted term
# is not itself mask-weighted, so re-tagging it would hand the deadness
# prover a value it never claimed was zero.


def _tag_transpose(ct, x, **params):
    if params.get("kind") == "declass":
        return [tag_p.bind(ct, **params)]
    return [ct]


ad.deflinear2(tag_p, _tag_transpose)
batching.defvectorized(tag_p)
mlir.register_lowering(tag_p, lambda ctx, x, **_: [x])

_STATE = threading.local()


def auditing() -> bool:
    """True inside an :func:`audit_tracing` context (this thread)."""
    return getattr(_STATE, "depth", 0) > 0


@contextmanager
def audit_tracing():
    """Enable tag emission for the duration (re-entrant, thread-local).
    Wrap ``jax.make_jaxpr(...)`` calls only -- see module docstring."""
    _STATE.depth = getattr(_STATE, "depth", 0) + 1
    try:
        yield
    finally:
        _STATE.depth -= 1


def tag(x, kind: str, channel: str, client_axis=None):
    """Identity, plus an IR marker when an audit trace is active.

    kind="declass"  x is a declared cross-client channel value: the
                    taint pass clears client-source taint here.
    kind="term"     x is a mask-weighted per-client term (client axis
                    ``client_axis``): the deadness pass proves its dead
                    padded slots are structural zeros.

    ``client_axis`` indexes an axis of ``x`` *at the call site*; call
    sites sit outside any vmap so the index survives into the jaxpr
    unshifted.
    """
    if not auditing():
        return x
    return tag_p.bind(x, kind=kind, channel=channel,
                      client_axis=client_axis)
