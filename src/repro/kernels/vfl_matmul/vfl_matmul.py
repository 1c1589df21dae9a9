"""Block-sparse VFL input matmul -- the TPU-native form of De-VertiFL's
zero-padding (DESIGN.md section 2).

The paper's client multiplies a zero-padded full-width input x' by the
first-layer weight W: y = zeropad(x_local) @ W. All rows of W outside
the client's feature slice meet zeros; a dense matmul wastes
(n_clients-1)/n_clients of the MXU work. This kernel computes
y = x_local @ W[offset:offset+K_local] instead: the padded input is
never built, and the rows outside the slice are never multiplied.

Grid: (cdiv(M, bm),) over row blocks. Each step takes a (bm, K_local)
block of the client's input -- the whole slice width -- and the whole
[K_full, N] weight, whose block index never changes, so it is copied
into VMEM once. The kernel slices the client's rows out of it with a
static ``pl.ds(offset, K_local)``. Client slices of the canonical
layouts (mnist rows of 28 or 98 columns, bank's 17-column thirds,
skewed splits) start and end anywhere, so the slice lives inside the
kernel: the TPU compiler accepts an unaligned static slice of a VMEM
ref, but not a BlockSpec block whose last two dims are neither
multiples of (8, 128) nor the array's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM the two double-buffered input blocks and the output block may
# take; beyond it the layout is refused by name instead of by the
# compiler (v5e scoped VMEM defaults to 16 MiB)
VMEM_BUDGET = 12 * 1024 * 1024


def _tile_bytes(rows, cols, itemsize):
    """VMEM footprint of a [rows, cols] block padded to (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * itemsize


def _kernel(x_ref, w_ref, o_ref, *, offset, k_local):
    w = w_ref[pl.ds(offset, k_local), :]
    o_ref[...] = jnp.dot(x_ref[...], w,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def vfl_matmul_p(x_local, w_full, offset: int, *, bm=128, interpret=False):
    """x_local: [M, K_local] (client's features, contiguous slice);
    w_full: [K_full, N]; offset: static slice start.
    Returns zeropad(x_local) @ w_full == x_local @ w_full[offset:...]."""
    M, K_local = x_local.shape
    K_full, N = w_full.shape
    if not 0 <= offset <= K_full - K_local:
        raise ValueError(f"client slice [{offset}, {offset + K_local}) "
                         f"lies outside W's {K_full} rows")
    bm = min(bm, M)
    item = x_local.dtype.itemsize
    vmem = (2 * _tile_bytes(bm, K_local, item)
            + 2 * _tile_bytes(K_full, N, w_full.dtype.itemsize)
            + 2 * _tile_bytes(bm, N, item))
    if vmem > VMEM_BUDGET:
        raise ValueError(
            f"vfl_matmul layout (M={M}, K_local={K_local}, "
            f"offset={offset}, K_full={K_full}, N={N}, bm={bm}) needs "
            f"{vmem} B of VMEM, over the {VMEM_BUDGET} B budget")
    kernel = functools.partial(_kernel, offset=offset, k_local=K_local)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(M, bm),),
        in_specs=[
            pl.BlockSpec((bm, K_local), lambda i: (i, 0)),
            pl.BlockSpec((K_full, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x_local.dtype),
        interpret=interpret,
        name="vfl_matmul",
    )(x_local, w_full)
