"""Public jit'd wrapper for decode attention (model layout adapter)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_grouped


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention(q, k, v, pos, *, block_kv: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, 1, H, hd) one new token; k, v: (B, W, K, hd) ring cache;
    pos: (W,) or (B, W) slot positions (-1 empty).  Returns (B, 1, H, hd).
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, _, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    if pos.ndim == 1:
        pos = jnp.broadcast_to(pos[None], (B, pos.shape[0]))
    pad = (-hd) % 128 if not interpret else 0
    sm_scale = hd ** -0.5
    if pad:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad)])
        k = jnp.pad(k, [(0, 0)] * 3 + [(0, pad)])
        v = jnp.pad(v, [(0, 0)] * 3 + [(0, pad)])
    # kernel layout (see decode_attention_grouped): head-major caches,
    # pos split into one (1, block_kv) row per kv block.  The transposes
    # read and write the whole cache once more per call; a decode loop
    # with a persistent cache should keep it head-major (B, K, W, hd) and
    # call decode_attention_grouped directly.
    W = k.shape[1]
    block_kv = min(block_kv, W)
    assert W % block_kv == 0
    qg = q[:, 0].reshape(B, K, G, q.shape[-1])
    kk = k.transpose(0, 2, 1, 3)
    vk = v.transpose(0, 2, 1, 3)
    posk = pos.reshape(B, W // block_kv, 1, block_kv)
    out = decode_attention_grouped(qg, kk, vk, posk, block_kv=block_kv,
                                   sm_scale=sm_scale, interpret=interpret)
    out = out.reshape(B, 1, H, out.shape[-1])
    if pad:
        out = out[..., :hd]
    return out
