"""jit'd public wrapper + custom VJP for the VFL block-sparse matmul.

The forward is the Pallas kernel (vfl_matmul_p): y = zeropad(x_local)
@ w_full computed as x_local @ w_full[offset:offset+K_local] by
indexing W's row blocks, never materializing the padding.  The VJP
keeps the same block-sparse structure:

  dx = g @ w_full[offset:offset+K_local].T      (sliced, never padded)
  dW = scatter-add of x_local.T @ g into W's rows
       [offset, offset+K_local) -- all other rows get an exact zero
       gradient, the same zeros the dense zeropad formulation produces
       (rows outside the slice only ever meet zero inputs).

Both cotangents are accumulated in fp32 and cast back, matching the
kernel's fp32 VMEM accumulator.

Padded-client gating: ``vfl_matmul(..., gate=g)`` multiplies the
output by a traced scalar (a client_mask entry).  Because the gate is
applied *outside* the custom VJP, autodiff scales both cotangents by
it -- dx = (g_ct * gate) @ W_slice.T and dW = scatter(x.T @ (g_ct *
gate)) -- so a masked-out (dead) client lane produces an exact-zero dW
scatter and dx without a Python-level branch.  gate=1.0 is a bitwise
identity on y, dx, and dW.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.vfl_matmul.vfl_matmul import vfl_matmul_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _vfl_matmul(x_local, w_full, offset, bm, interpret):
    return vfl_matmul_p(x_local, w_full, offset, bm=bm, interpret=interpret)


def _vfl_matmul_fwd(x_local, w_full, offset, bm, interpret):
    y = _vfl_matmul(x_local, w_full, offset, bm, interpret)
    return y, (x_local, w_full)


def _vfl_matmul_bwd(offset, bm, interpret, res, g):
    x_local, w_full = res
    k_local = x_local.shape[1]
    w_slice = jax.lax.slice_in_dim(w_full, offset, offset + k_local,
                                   axis=0)
    g32 = g.astype(jnp.float32)
    dx = (g32 @ w_slice.astype(jnp.float32).T).astype(x_local.dtype)
    dw_block = x_local.astype(jnp.float32).T @ g32
    dw = (jnp.zeros(w_full.shape, jnp.float32)
          .at[offset:offset + k_local].add(dw_block)
          .astype(w_full.dtype))
    return dx, dw


_vfl_matmul.defvjp(_vfl_matmul_fwd, _vfl_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("offset", "bm", "interpret"))
def vfl_matmul(x_local, w_full, offset: int, *, gate=None, bm=128,
               interpret=None):
    """y = zeropad(x_local) @ w_full without materializing the padding.

    Differentiable (custom VJP above). interpret=None runs the compiled
    kernel on a TPU and the Pallas interpreter elsewhere
    (``repro.kernels.interpret_default``).

    gate: optional traced scalar (e.g. a LayoutArrays.client_mask
    entry) multiplied into the output; gate=0.0 zeroes y AND both
    gradients (the dW scatter rows come out exactly zero), gate=1.0 is
    a bitwise no-op.  This is how padded federations mask dead client
    lanes through the kernel path.
    """
    if interpret is None:
        interpret = interpret_default()
    y = _vfl_matmul(x_local, w_full, offset, bm, interpret)
    if gate is not None:
        y = y * jnp.asarray(gate, y.dtype)
    return y
