"""Pallas TPU kernel: Mamba-2 SSD intra-chunk pass (arXiv:2405.21060).

Computes, per (batch, chunk, head-block) grid cell, the quadratic
intra-chunk output, the chunk's outgoing state contribution, and the chunk
decay — the three quantities the (cheap, jnp-level) inter-chunk recurrence in
``ops.py`` stitches together.  This mirrors how the reference CUDA/Triton
implementation splits into chunk_scan / chunk_state kernels, re-tiled for
VMEM: with (Q=256, bh=8, P=64, N≤128) the working set is ≈6 MB fp32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, g_ref, *,
                chunk: int):
    # head-major blocks: the head block is a leading (batch) dim and the
    # chunk / state widths sit on the sublane and lane dims
    x = x_ref[0].astype(jnp.float32)      # [bh, Q, P]
    dt = dt_ref[0].astype(jnp.float32)    # [bh, Q]
    a = a_ref[...].astype(jnp.float32)    # [bh, 1]
    bm = b_ref[0].astype(jnp.float32)     # [Q, N]
    cm = c_ref[0].astype(jnp.float32)     # [Q, N]
    bh = x.shape[0]

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da = dt * a                           # [bh, Q]
    # prefix sum as a matmul with the upper-triangular ones (Mosaic has
    # no cumsum): cum[h, q] = sum_{s <= q} da[h, s]
    cum = jax.lax.dot(da, (qi <= si).astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)  # [bh, Q]

    # intra-chunk quadratic part
    rel = cum[:, :, None] - cum[:, None, :]          # [bh, q, s]
    tri = (si <= qi)[None]
    decay = jnp.where(tri, jnp.exp(rel), 0.0)        # [bh, q, s]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [q, s]
    m = cb[None] * decay * dt[:, None, :]            # [bh, q, s]
    # y[h,q,p] = sum_s m[h,q,s] x[h,s,p]  — batched over h
    y = jax.lax.dot_general(m, x, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)  # [bh,q,P]
    y_ref[0] = y.astype(y_ref.dtype)

    # chunk state: st[h,p,n] = sum_s exp(cum_Q - cum_s) dt_s x[h,s,p] B[s,n]
    dec_out = jnp.exp(cum[:, -1:] - cum) * dt        # [bh, Q]
    xw = x * dec_out[:, :, None]                     # [bh, Q, P]
    bmb = jnp.broadcast_to(bm[None], (bh,) + bm.shape)
    st = jax.lax.dot_general(xw, bmb, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)  # [bh,P,N]
    st_ref[0] = st.astype(st_ref.dtype)
    g_ref[0] = jnp.exp(cum[:, -1:]).astype(g_ref.dtype)   # [bh, 1]


def ssd_intra_chunk(x, dt, a, bmat, cmat, *, bh: int = 8,
                    interpret: bool = False):
    """x: [B, L, H, P] · dt: [B, L, H] · a: [H] · bmat/cmat: [B, L, N].

    L must be a multiple of ``chunk`` = the caller's chunk size — here the
    grid is (B·nc, H/bh) with one chunk per grid row, so the caller reshapes
    L into chunks first.  Returns (y_intra [B,L,H,P], states [B,nc,H,P,N],
    decays [B,nc,H]).

    The kernel works head-major (x as [B, H, L, P], dt as [B, H, L]): a
    head block of ``bh`` then never lands on the lane dim, which the TPU
    tiling rule (last two block dims divisible by 8 and 128, or whole)
    refuses for head counts such as mamba2-780m's 48.
    """
    b, l, h, p = x.shape
    n = bmat.shape[-1]
    chunk = l  # caller pre-chunks: one call handles [B*nc, chunk, ...]
    bh = min(bh, h)
    assert h % bh == 0

    grid = (b, h // bh)
    y, st, g = pl.pallas_call(
        partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, chunk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bh, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, chunk, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, p, n), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bh, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, chunk, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a.reshape(h, 1),
      bmat, cmat)
    return y.transpose(0, 2, 1, 3), st, g[..., 0]
