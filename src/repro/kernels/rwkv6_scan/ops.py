"""jit'd public wrapper for the RWKV6 WKV scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.rwkv6_scan.rwkv6_scan import rwkv6_scan_p


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, *, chunk=64, interpret=None):
    """RWKV6 recurrence; interpret=None resolves through
    ``repro.kernels.interpret_default``."""
    if interpret is None:
        interpret = interpret_default()
    return rwkv6_scan_p(r, k, v, w, u, chunk=chunk, interpret=interpret)
