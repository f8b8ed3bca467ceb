"""Single-token GQA decode attention over a (ring) KV cache.

The decode step is memory-bound: every step streams the whole cache from
HBM once.  The kernel's job is (a) to touch each cache byte exactly once,
and (b) to keep the MXU busy despite Sq == 1 — so the q heads sharing a kv
head are grouped into a (group x block_kv) matmul instead of G rank-1
products (DESIGN.md §4, TPU adaptation).

Grid (batch, kv_heads, kv_blocks); scratch carries the online-softmax state
across kv blocks.  Ring-buffer semantics come for free: the cache's
position array marks empty slots with -1 and the kernel masks on pos >= 0 —
no scalar arguments needed (windowing is enforced by the ring buffer
itself, which only retains the last W positions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   n_kv_blocks: int, sm_scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = pos_ref[0, 0]                                 # (1, bkv)

    s = q @ k.T                                         # (G, bkv)
    valid = pos >= 0
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + p @ v
    m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l_fin = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l_fin).astype(o_ref.dtype)


def decode_attention_grouped(q, k, v, pos, *, block_kv: int,
                             sm_scale: float, interpret: bool = True):
    """Kernel layout: q (B, K, G, hd) one token per batch, G = q-heads
    per kv head; k, v (B, K, W, hd) ring caches, head-major; pos
    (B, n_kv, 1, block_kv) slot positions (-1 empty), one row per kv
    block.  Every block's last two dims are either (8, 128)-aligned or
    the whole array dims, as the TPU lowering requires.

    Returns (B, K, G, hd).  ``ops.decode_attention`` adapts the model
    layout.
    """
    B, K, G, hd = q.shape
    W = k.shape[2]
    n_kv = W // block_kv
    assert n_kv * block_kv == W and pos.shape == (B, n_kv, 1, block_kv)

    kernel = functools.partial(_decode_kernel, n_kv_blocks=n_kv,
                               sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid=(B, K, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, h, ki: (b, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, pos)
