"""Public jit'd wrapper for the SSD chunked-scan kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.ssd_scan.kernel import ssd_scan_chunked


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, adt, dt, B, C, *, chunk: int = 256,
             interpret: Optional[bool] = None) -> jax.Array:
    """Mamba2 SSD: x (Bsz,S,H,hp); adt/dt (Bsz,S,H); B/C (Bsz,S,N)."""
    if interpret is None:
        interpret = not _on_tpu()
    Bsz, S, H, hp = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    # kernel layout (see ssd_scan_chunked): head-major chunks, each
    # chunk's adt/dt as a (1, L) row
    xk = x.reshape(Bsz, nc, chunk, H, hp).transpose(0, 3, 1, 2, 4)
    adtk = adt.reshape(Bsz, nc, 1, chunk, H).transpose(0, 4, 1, 2, 3)
    dtk = dt.reshape(Bsz, nc, 1, chunk, H).transpose(0, 4, 1, 2, 3)
    Bk = B.reshape(Bsz, nc, chunk, N)
    Ck = C.reshape(Bsz, nc, chunk, N)
    yk = ssd_scan_chunked(xk, adtk, dtk, Bk, Ck, interpret=interpret)
    return yk.transpose(0, 2, 3, 1, 4).reshape(Bsz, S, H, hp)
