"""jit'd public wrapper for flash attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_default
from repro.kernels.flash_attention.flash_attention import flash_attention_p


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=0.0,
                    scale=None, bq=128, bk=128, interpret=None):
    """Flash attention; interpret=None resolves through
    ``repro.kernels.interpret_default``."""
    if interpret is None:
        interpret = interpret_default()
    return flash_attention_p(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, bq=bq, bk=bk,
                             interpret=interpret)
