"""Mamba2 SSD chunked scan kernel (state-space duality, arXiv:2405.21060).

TPU-native schedule (DESIGN.md §4): the sequence is split into chunks of
length L; all *intra-chunk* work is dense (L x L) and (L x d_state)
matmuls that feed the MXU, and the *inter-chunk* recurrence carries a
(head_dim x d_state) state in VMEM scratch across the sequential chunk
grid dimension — the TPU analogue of the CUDA selective-scan, with the
parallel-scan replaced by the grid's guaranteed sequential order.

Grid: (batch, heads, n_chunks).  Per-step VMEM: chunk inputs
(L x head_dim + 2 L x d_state + 2 L) + state (head_dim x d_state) fp32
~ 0.5 MB at L=256, hp=64, N=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, adt_ref, dt_ref, b_ref, c_ref, y_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0, 0].astype(jnp.float32)     # (L, hp)
    adt = adt_ref[0, 0, 0].astype(jnp.float32)  # (1, L) row
    dt = dt_ref[0, 0, 0].astype(jnp.float32)   # (1, L) row
    B = b_ref[0, 0].astype(jnp.float32)        # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)        # (L, N)

    # The TPU lowering has no cumsum and no 1-D vectors: prefix sums and
    # row->column turns are masked lane/sublane reductions over (L, L)
    # (exact f32 adds, no MXU pass that could round to bf16).
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    adt_col = jnp.sum(jnp.where(li == lj, adt, 0.0), axis=1, keepdims=True)
    dt_col = jnp.sum(jnp.where(li == lj, dt, 0.0), axis=1, keepdims=True)
    cum_col = jnp.sum(jnp.where(causal, adt, 0.0), axis=1,
                      keepdims=True)          # (L, 1): cum_i
    cum_row = jnp.sum(jnp.where(li <= lj, adt_col, 0.0), axis=0,
                      keepdims=True)          # (1, L): cum_j
    total = jnp.sum(adt, axis=1, keepdims=True)  # (1, 1): cum_L

    # intra-chunk: scores[i, j] = (C_i . B_j) * exp(cum_i - cum_j) * (i >= j)
    decay = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)
    cb = C @ B.T                               # (L, L)
    y_intra = (cb * decay) @ (x * dt_col)

    # inter-chunk: y_i += (C_i * exp(cum_i)) @ h_prev^T
    h_prev = h_scr[...]                        # (hp, N)
    y_inter = (C * jnp.exp(cum_col)) @ h_prev.T

    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = h * exp(cum_L) + sum_j exp(cum_L - cum_j) dt_j x_j B_j^T
    xw = x * (jnp.exp(total - cum_col) * dt_col)  # (L, hp)
    h_scr[...] = h_prev * jnp.exp(total) + jax.lax.dot_general(
        xw, B, (((0,), (0,)), ((), ())))       # xw^T @ B: (hp, N)


def ssd_scan_chunked(xk, adtk, dtk, Bk, Ck, *,
                     interpret: bool = True) -> jax.Array:
    """Kernel layout: xk (Bsz, H, nc, L, hp); adtk, dtk (Bsz, H, nc, 1, L);
    Bk, Ck (Bsz, nc, L, N).  Every block's last two dims are either
    (8, 128)-aligned or the whole array dims, as the TPU lowering
    requires — hence the unit axis that makes each chunk's decay row a
    whole (1, L) tile.

    Returns y (Bsz, H, nc, L, hp).  n_groups = 1 (B/C shared across
    heads).  ``ops.ssd_scan`` adapts the model layout.
    """
    Bsz, H, nc, chunk, hp = xk.shape
    N = Bk.shape[-1]

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, hp),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, hp),
                               lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, nc, chunk, hp), xk.dtype),
        scratch_shapes=[pltpu.VMEM((hp, N), jnp.float32)],
        interpret=interpret,
    )(xk, adtk, dtk, Bk, Ck)
