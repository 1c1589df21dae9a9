"""jit'd public wrapper for the Mamba selective-scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.mamba_scan.mamba_scan import mamba_scan_p


@functools.partial(jax.jit, static_argnames=("bd", "chunk", "interpret"))
def mamba_scan(a, bx, c, *, bd=512, chunk=64, interpret=None):
    """Selective scan; interpret=None resolves through
    ``repro.kernels.interpret_default``."""
    if interpret is None:
        interpret = interpret_default()
    return mamba_scan_p(a, bx, c, bd=bd, chunk=chunk, interpret=interpret)
