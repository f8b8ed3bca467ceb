"""Kernel-backed cascade students (the real-model levels, §ROADMAP).

Two students that put the Pallas kernels on the cascade's serving path:

* ``tinytf_flash`` — a *causal* tiny-transformer classifier whose
  per-layer attention runs through ``kernels.flash_attention`` and whose
  classification readout is a learned-query attention pool through
  ``kernels.decode_attention`` (the ring-cache ``pos`` mask gives exact
  pad exclusion for free).  Causality is what makes the kernel usable:
  pads sit at the END of a ``hash_ids`` buffer, a causal mask means no
  real token ever attends to a pad, and the pooled readout drops the pad
  positions — so real-token logits are provably pad-independent without
  the hand-rolled key-mask of ``students.tinytf_logits``.
* ``ssm`` — an embedded Mamba2 stack (``models.ssm``) whose inner SSD
  scan runs through ``kernels.ssd_scan``.

Both expose a ``use_kernels`` switch selecting between the Pallas path
(``kernels/*/ops.py``; interpret-mode on CPU) and the pure-jnp reference
path (``kernels/*/ref.py`` / ``models.ssm.ssd_chunked``).  The serving
route pass predicts through the kernel path; the online-imitation loss
differentiates through the reference path — ``pallas_call`` has no VJP,
and the two paths are tolerance-pinned equal (tests/test_kernel_levels.py)
so the gradient is taken on the same math the kernels compute.  Both
paths run every f32 matmul, inside the kernels too, at full float32
precision (``MATMUL_PRECISION``): the TPU's default one-pass bf16 would
put the paths ~1e-3 apart and serve a different model than the one
trained.

Shape/dtype contract (all float32 activations):
  tokens : (B, L) int32 hashed ids from ``data.features.hash_ids``;
           0 = pad, pads only at the end; L = spec.max_len.
  logits : (B, n_classes) float32.
Block constraints: ``max_len`` must be divisible by ``block_q`` /
``block_kv`` (flash) and by ``chunk`` (SSD) after each is min'd to the
sequence length — powers of two keep every default legal.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.kernels.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.models.layers import dense_init
from repro.models.ssm import init_mamba, mamba_forward, ssd_chunked

# f32 matmul precision of both students' forward passes, on both paths
MATMUL_PRECISION = "float32"


def _at_matmul_precision(fn):
    """Trace ``fn`` under ``MATMUL_PRECISION`` (a fresh context per call:
    one shared context object would not nest)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args, **kwargs)
    return wrapped


@dataclass(frozen=True)
class TinyTFFlashSpec:
    """Causal tiny transformer on the flash/decode kernel path.

    ``d_model`` must divide by ``n_heads``; ``max_len`` must divide by
    ``block_q``/``block_kv`` (after min'ing to the sequence — powers of
    two are always safe).  Head dim below 128 is zero-padded to the MXU
    lane width inside the ops wrapper on TPU (free on CPU interpret).
    """

    vocab: int = 4096          # hashed token ids (0 = pad)
    max_len: int = 128
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    n_classes: int = 2
    block_q: int = 64          # flash q-tile (VMEM block rows)
    block_kv: int = 64         # flash/decode kv-tile


@dataclass(frozen=True)
class SSMStudentSpec:
    """Embedded Mamba2 classifier on the ``ssd_scan`` kernel path.

    ``expand * d_model`` must divide by ``head_dim``; ``max_len`` must
    divide by ``chunk`` (after min'ing to the sequence).  Sized one
    capability notch above the flash transformer in the default kernel
    ladder (metrics.costs keeps the c_i ordering honest).
    """

    vocab: int = 4096
    max_len: int = 128
    d_model: int = 192
    d_state: int = 32          # N, the SSD state width
    d_conv: int = 4
    expand: int = 2            # d_inner = expand * d_model
    head_dim: int = 64
    chunk: int = 64            # SSD chunk length (VMEM tile)
    n_layers: int = 2
    n_classes: int = 2


# CI-sized specs: the smallest shapes the kernels' tiling constraints
# allow.  Interpret-mode Pallas on CPU is an emulation, so the tier-1
# parity tests, benchmarks/kernel_levels.py, and ``serve.py --ladder
# kernel-ci`` all run these instead of the defaults above.
TINY_TF_CI = TinyTFFlashSpec(vocab=256, max_len=32, d_model=32, n_heads=2,
                             n_layers=1, d_ff=64, block_q=16, block_kv=16)
TINY_SSM_CI = SSMStudentSpec(vocab=256, max_len=32, d_model=16, d_state=8,
                             expand=2, head_dim=16, chunk=16, n_layers=1)


def _ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * scale


# ---------------------------------------------------------------------------
# tinytf_flash: causal transformer, flash-attention layers, decode readout
# ---------------------------------------------------------------------------
def tinytf_flash_init(key, spec: TinyTFFlashSpec):
    """Initialize params: embed/pos tables, per-layer attn+FF, readout.

    The readout is a learned per-head query ``ro_q`` (H, hd) plus k/v
    projections — classification = one decode-attention step over the
    final hidden states.  Classifier head starts at zero like every
    other student (the cascade learns it online)."""
    ks = jax.random.split(key, 3 + spec.n_layers)
    d, f, H = spec.d_model, spec.d_ff, spec.n_heads
    hd = d // H
    params = {
        "embed": jax.random.normal(ks[0], (spec.vocab, d)) * 0.02,
        "pos": jax.random.normal(ks[1], (spec.max_len, d)) * 0.02,
        "layers": [],
        "ro_q": jax.random.normal(ks[2], (H, hd)) * 0.02,
        "ro_wk": dense_init(jax.random.fold_in(ks[2], 1), d, d, jnp.float32),
        "ro_wv": dense_init(jax.random.fold_in(ks[2], 2), d, d, jnp.float32),
        "ln_f": jnp.ones((d,), jnp.float32),
        "cls_w": jnp.zeros((d, spec.n_classes), jnp.float32),
        "cls_b": jnp.zeros((spec.n_classes,), jnp.float32),
    }
    layers = []
    for i in range(spec.n_layers):
        lk = jax.random.split(ks[3 + i], 5)
        layers.append({
            "wq": dense_init(lk[0], d, d, jnp.float32),
            "wk": dense_init(lk[1], d, d, jnp.float32),
            "wv": dense_init(lk[2], d, d, jnp.float32),
            "wo": dense_init(lk[3], d, d, jnp.float32),
            "w1": dense_init(lk[4], d, f, jnp.float32),
            "w2": dense_init(jax.random.fold_in(lk[4], 1), f, d, jnp.float32),
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
        })
    params["layers"] = layers
    return params


def _causal_attend(q, k, v, spec: TinyTFFlashSpec, use_kernels: bool):
    """One causal attention, (B, L, H, hd) in and out.

    Kernel path: ``flash_attention`` (online-softmax Pallas kernel, its
    native layout).  Ref path: the jnp oracle ``attention_ref`` (B, H,
    S, hd layout) — differentiable, tolerance-equal."""
    if use_kernels:
        return flash_attention(q, k, v, causal=True,
                               block_q=spec.block_q, block_kv=spec.block_kv)
    out = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    return out.transpose(0, 2, 1, 3)


def _pool_readout(hf, pos_ids, params, spec: TinyTFFlashSpec,
                  use_kernels: bool):
    """Learned-query attention pool over valid positions -> (B, d).

    The final hidden states are the "ring cache", the learned query is
    the "new token", and ``pos_ids`` (-1 on pads) is exactly the decode
    kernel's empty-slot mask — pad exclusion without a separate mask
    tensor."""
    B, L, d = hf.shape
    H = spec.n_heads
    hd = d // H
    k = (hf @ params["ro_wk"]).reshape(B, L, H, hd)
    v = (hf @ params["ro_wv"]).reshape(B, L, H, hd)
    q = jnp.broadcast_to(params["ro_q"][None, None], (B, 1, H, hd))
    if use_kernels:
        pooled = decode_attention(q, k, v, pos_ids,
                                  block_kv=spec.block_kv)[:, 0]
    else:
        pooled = decode_attention_ref(
            q[:, 0].reshape(B, H, 1, hd), k, v, pos_ids).reshape(B, H, hd)
    return pooled.reshape(B, d)


@_at_matmul_precision
def tinytf_flash_logits(params, tokens, spec: TinyTFFlashSpec,
                        use_kernels: bool = True):
    """tokens: (B, L) int32, 0 = pad (pads at the end) -> (B, C) logits.

    ``use_kernels=True`` runs flash attention + the decode-attention
    readout (serving route pass); ``False`` runs the jnp reference path
    (the differentiable loss path — ``pallas_call`` has no VJP)."""
    B, L = tokens.shape
    mask = tokens > 0
    h = params["embed"][tokens] + params["pos"][None, :L]
    H = spec.n_heads
    hd = spec.d_model // H
    for lp in params["layers"]:
        x = _ln(h, lp["ln1"])
        q = (x @ lp["wq"]).reshape(B, L, H, hd)
        k = (x @ lp["wk"]).reshape(B, L, H, hd)
        v = (x @ lp["wv"]).reshape(B, L, H, hd)
        att = _causal_attend(q, k, v, spec, use_kernels)
        h = h + att.reshape(B, L, spec.d_model) @ lp["wo"]
        x = _ln(h, lp["ln2"])
        h = h + jax.nn.gelu(x @ lp["w1"]) @ lp["w2"]
    hf = _ln(h, params["ln_f"])
    # position 0 stays valid even for an empty doc so the readout
    # softmax never sees an all-masked row
    ar = jnp.arange(L)
    pos_ids = jnp.where(mask | (ar == 0)[None], ar[None], -1)
    pos_ids = jnp.broadcast_to(pos_ids, (B, L)).astype(jnp.int32)
    pooled = _pool_readout(hf, pos_ids, params, spec, use_kernels)
    return pooled @ params["cls_w"] + params["cls_b"]


def tinytf_flash_predict(params, tokens, spec: TinyTFFlashSpec):
    """Softmax class probabilities via the kernel path (route pass)."""
    return jax.nn.softmax(
        tinytf_flash_logits(params, tokens, spec, use_kernels=True), axis=-1)


def tinytf_flash_loss_weighted(params, tokens, labels, w,
                               spec: TinyTFFlashSpec):
    """Per-item-weighted xent on the differentiable reference path."""
    from repro.models.students import _weighted_xent
    logits = tinytf_flash_logits(params, tokens, spec, use_kernels=False)
    return _weighted_xent(logits, labels, w)


# ---------------------------------------------------------------------------
# ssm: embedded Mamba2 stack on the ssd_scan kernel path
# ---------------------------------------------------------------------------
def ssm_model_config(spec: SSMStudentSpec) -> ModelConfig:
    """The internal ``ModelConfig`` driving ``models.ssm`` for this
    student (one mamba block per layer, float32, no attention)."""
    return ModelConfig(
        name="ssm-student", family="ssm", n_layers=spec.n_layers,
        d_model=spec.d_model, d_ff=0, vocab=spec.vocab,
        ssm=SSMConfig(d_state=spec.d_state, d_conv=spec.d_conv,
                      expand=spec.expand, head_dim=spec.head_dim,
                      chunk=spec.chunk),
        period=("mamba",), dtype="float32")


def _ssd_kernel_impl(x, adt, dt, B, C, chunk, init_state=None):
    """``models.ssm.ssd_chunked``-shaped adapter over ``kernels.ssd_scan``
    (forward-only: the kernel carries no resumable state)."""
    assert init_state is None, "kernel SSD path is forward-only"
    return ssd_scan(x, adt, dt, B, C, chunk=chunk), None


def ssm_student_init(key, spec: SSMStudentSpec):
    """Initialize params: embed table, per-layer mamba blocks + norms,
    final norm, zero classifier head."""
    cfg = ssm_model_config(spec)
    ks = jax.random.split(key, 1 + spec.n_layers)
    d = spec.d_model
    return {
        "embed": jax.random.normal(ks[0], (spec.vocab, d)) * 0.02,
        "blocks": [init_mamba(ks[1 + i], cfg) for i in range(spec.n_layers)],
        "norms": [jnp.ones((d,), jnp.float32) for _ in range(spec.n_layers)],
        "ln_f": jnp.ones((d,), jnp.float32),
        "cls_w": jnp.zeros((d, spec.n_classes), jnp.float32),
        "cls_b": jnp.zeros((spec.n_classes,), jnp.float32),
    }


@_at_matmul_precision
def ssm_student_logits(params, tokens, spec: SSMStudentSpec,
                       use_kernels: bool = True):
    """tokens: (B, L) int32, 0 = pad (pads at the end) -> (B, C) logits.

    The mamba recurrence is causal, so masked-mean pooling over valid
    positions is pad-independent (trailing pads never feed a valid
    position's state).  ``use_kernels`` selects ``kernels.ssd_scan`` vs
    the jnp ``ssd_chunked`` oracle for the inner scan."""
    cfg = ssm_model_config(spec)
    impl = _ssd_kernel_impl if use_kernels else ssd_chunked
    mask = tokens > 0
    h = params["embed"][tokens]                          # (B, L, d) f32
    for blk, scale in zip(params["blocks"], params["norms"]):
        h = h + mamba_forward(blk, _ln(h, scale), cfg, ssd_impl=impl)
    hf = _ln(h, params["ln_f"])
    m = mask.astype(jnp.float32)[..., None]
    pooled = jnp.sum(hf * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
    return pooled @ params["cls_w"] + params["cls_b"]


def ssm_student_predict(params, tokens, spec: SSMStudentSpec):
    """Softmax class probabilities via the kernel path (route pass)."""
    return jax.nn.softmax(
        ssm_student_logits(params, tokens, spec, use_kernels=True), axis=-1)


def ssm_student_loss_weighted(params, tokens, labels, w,
                              spec: SSMStudentSpec):
    """Per-item-weighted xent on the differentiable reference path."""
    from repro.models.students import _weighted_xent
    logits = ssm_student_logits(params, tokens, spec, use_kernels=False)
    return _weighted_xent(logits, labels, w)
