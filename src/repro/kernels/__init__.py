"""Pallas TPU kernels for the serving hot spots (DESIGN.md §4).

The paper's cost center is LLM first-token inference (App. B.1: quadratic
attention prefill dominates, OOMs at batch 2 on 8xA100).  These kernels are
the TPU-native answer for the expert level of the cascade:

  flash_attention/  — prefill attention, causal + sliding-window + GQA
  decode_attention/ — single-token GQA attention over a (ring) KV cache
  moe_gmm/          — grouped expert matmul for MoE FFNs
  ssd_scan/         — Mamba2 chunked state-space-dual scan

Each kernel package ships three files:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper: model layout -> kernel layout,
              Mosaic on TPU, interpret mode on any other backend
  ref.py    — pure-jnp oracle used by the allclose test sweeps
"""
import re

_PALLAS_OP = re.compile(r"jit\((\w+)\)/pallas_call")


def mosaic_kernels(compiled_text: str) -> set:
    """Names of the jitted kernel wrappers (``flash_attention``,
    ``decode_attention``, ``ssd_scan``, ...) that a compiled program
    runs as Mosaic ``tpu_custom_call``s — read from
    ``jax.jit(f).lower(...).compile().as_text()``.  An interpret-mode
    kernel lowers to plain HLO and leaves no such call."""
    return {m.group(1) for line in compiled_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [_PALLAS_OP.search(line)] if m}
