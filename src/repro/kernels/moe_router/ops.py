"""jit'd public wrapper for the fused MoE router kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.moe_router.moe_router import moe_router_p


@functools.partial(jax.jit, static_argnames=("k", "bt", "interpret"))
def moe_router(logits, k, *, bt=128, interpret=None):
    """Fused softmax + top-k + renorm + aux stats; interpret=None
    resolves through ``repro.kernels.interpret_default``."""
    if interpret is None:
        interpret = interpret_default()
    return moe_router_p(logits, k, bt=bt, interpret=interpret)
