"""The serving path's Pallas kernels compile for a TPU v5e.

Every other kernel test runs in interpret mode, which accepts block
shapes and vector ops the chip's compiler refuses.  These tests compile
``flash_attention``, ``decode_attention`` and ``ssd_scan`` with
``interpret=False`` for a described (not attached) ``v5e:2x2`` topology,
at the shapes the kernel ladder's levels run at their default specs and
at the smallest and largest lane buckets of a 64-lane engine, both at
JAX's default matmul precision and at the full f32 precision the
students trace them under, and require each to come out as a Mosaic
``tpu_custom_call``.  Nothing
runs, so they say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers an
import-time load would give the workers different tests to collect.
Keep these tests in this one file, for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import mosaic_kernels
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.kernel_students import (MATMUL_PRECISION, SSMStudentSpec,
                                          TinyTFFlashSpec)

TF = TinyTFFlashSpec()
SSM = SSMStudentSpec()
BUCKETS = (8, 64)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology (skip if none)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _flash(b, shape):
    hd = TF.d_model // TF.n_heads
    qkv = shape((b, TF.max_len, TF.n_heads, hd))

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=TF.block_q,
                               block_kv=TF.block_kv, interpret=False)
    return f, (qkv, qkv, qkv)


def _decode(b, shape):
    # the tinytf_flash readout: one learned query per head over the
    # final hidden states, pads masked by pos = -1
    hd = TF.d_model // TF.n_heads
    kv = shape((b, TF.max_len, TF.n_heads, hd))

    def f(q, k, v, pos):
        return decode_attention(q, k, v, pos, block_kv=TF.block_kv,
                                interpret=False)
    return f, (shape((b, 1, TF.n_heads, hd)), kv, kv,
               shape((b, TF.max_len), jnp.int32))


def _ssd(b, shape):
    heads = SSM.expand * SSM.d_model // SSM.head_dim
    per_head = shape((b, SSM.max_len, heads))
    bc = shape((b, SSM.max_len, SSM.d_state))

    def f(x, adt, dt, bm, cm):
        return ssd_scan(x, adt, dt, bm, cm, chunk=SSM.chunk,
                        interpret=False)
    return f, (shape((b, SSM.max_len, heads, SSM.head_dim)), per_head,
               per_head, bc, bc)


KERNELS = {"flash_attention": _flash, "decode_attention": _decode,
           "ssd_scan": _ssd}


@pytest.mark.parametrize("precision", [None, MATMUL_PRECISION])
@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_to_mosaic(one_chip, name, bucket, precision):
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = KERNELS[name](bucket, shape)
    with jax.default_matmul_precision(precision):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert mosaic_kernels(text) == {name}
