"""Top-k MoE with GShard-style capacity dispatch.

Two execution paths:

* **local**: plain jnp one-hot dispatch on whatever token block the caller
  holds.  Used on single-device (tests / CPU experiments) and as the
  per-shard body of the distributed path.
* **sharded**: ``shard_map`` over the mesh.  Tokens are sharded over
  ('pod','data'); expert weights are sharded over 'model' either on the
  expert-ff dim (``sharding_mode='tensor'``, default) or on the expert dim
  (``'expert'``, requires num_experts % model_axis == 0).  Both modes finish
  with a single psum over 'model' — the hand-scheduled analogue of
  tensor-parallel MLP collectives (see DESIGN.md §4: no NCCL semantics, just
  jax.lax collectives inside shard_map).

Aux losses (load-balance + router-z) are returned for the training objective.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs.base import ModelConfig
from repro.models import flags
from repro.models.layers import dense_init, trunc_normal


def init_moe(key, cfg: ModelConfig):
    """Router + per-expert SwiGLU weights, stacked on a leading E axis."""
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_in": trunc_normal(ks[1], (e, d, f), d ** -0.5, cfg.jnp_dtype),
        "w_gate": trunc_normal(ks[2], (e, d, f), d ** -0.5, cfg.jnp_dtype),
        "w_out": trunc_normal(ks[3], (e, f, d), f ** -0.5, cfg.jnp_dtype),
    }


def capacity_for(n_tokens: int, cfg: ModelConfig) -> int:
    """Per-expert token capacity (top_k * T / E * factor, rounded to 4)."""
    m = cfg.moe
    c = math.ceil(m.top_k * n_tokens / m.num_experts * m.capacity_factor)
    return max(4, ((c + 3) // 4) * 4)


def route(x2d, router_w, cfg: ModelConfig):
    """x2d: (T, D) -> top-k indices/weights + aux losses (fp32)."""
    m = cfg.moe
    logits = (x2d.astype(jnp.float32) @ router_w)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, m.top_k)          # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # load-balance loss (Switch): E * sum_e f_e * p_e
    assign = jax.nn.one_hot(top_idx, m.num_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(jnp.sum(assign, axis=1), axis=0)  # (E,)
    frac_probs = jnp.mean(probs, axis=0)
    lb = m.num_experts * jnp.sum(frac_tokens * frac_probs) * m.load_balance_weight
    zl = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_weight
    return top_idx, top_w, lb + zl


def _dispatch_combine(top_idx, top_w, n_tokens: int, capacity: int,
                      cfg: ModelConfig):
    """Build (T, E, C) dispatch (0/1) and combine (gated) tensors."""
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    # Sequential slot priority: earlier top-k slots claim queue positions
    # first (GShard §3.2).
    dispatch = jnp.zeros((n_tokens, E, capacity), jnp.float32)
    combine = jnp.zeros((n_tokens, E, capacity), jnp.float32)
    used = jnp.zeros((E,), jnp.int32)
    for slot in range(k):
        mask = jax.nn.one_hot(top_idx[:, slot], E, dtype=jnp.int32)  # (T, E)
        pos = jnp.cumsum(mask, axis=0) - 1 + used[None, :]           # (T, E)
        keep = (pos < capacity) & (mask > 0)
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)    # (T, E, C)
        sel = keep.astype(jnp.float32)[..., None] * pos_oh
        dispatch = dispatch + sel
        combine = combine + sel * top_w[:, slot][:, None, None]
        used = used + jnp.sum(mask, axis=0)
    return dispatch, combine


def _expert_ffn(inp, params, cfg: ModelConfig):
    """inp: (E, C, D) -> (E, C, D) through each expert's SwiGLU."""
    h = jnp.einsum("ecd,edf->ecf", inp, params["w_in"])
    g = jnp.einsum("ecd,edf->ecf", inp, params["w_gate"])
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"])


MOE_GROUP = 2048  # tokens per dispatch group (GShard 'group size')


def _moe_group(x2d, params, cfg: ModelConfig, capacity: int):
    top_idx, top_w, aux = route(x2d, params["router"], cfg)
    dispatch, combine = _dispatch_combine(top_idx, top_w, x2d.shape[0],
                                          capacity, cfg)
    inp = jnp.einsum("tec,td->ecd", dispatch,
                     x2d.astype(jnp.float32)).astype(cfg.jnp_dtype)
    out = _expert_ffn(inp, params, cfg)
    y = jnp.einsum("tec,ecd->td", combine, out.astype(jnp.float32))
    return y.astype(x2d.dtype), aux


def moe_ffn_local(x2d, params, cfg: ModelConfig, capacity: int = None):
    """Single-shard GShard MoE: x2d (T, D) -> (y (T, D), aux loss).

    Tokens are processed in groups of MOE_GROUP: capacity (and therefore
    the (T, E, C) dispatch one-hot) scales with the group, not the full
    shard — without grouping the dispatch einsum is O(T^2) and dwarfs the
    expert matmuls at training token counts (65k tokens/shard -> the
    dispatch alone would be ~20x the expert FLOPs)."""
    T = x2d.shape[0]
    if T <= MOE_GROUP or T % MOE_GROUP != 0:
        capacity = capacity or capacity_for(T, cfg)
        return _moe_group(x2d, params, cfg, capacity)
    n_groups = T // MOE_GROUP
    cap = capacity or capacity_for(MOE_GROUP, cfg)
    xg = x2d.reshape(n_groups, MOE_GROUP, -1)

    def body(_, xb):
        y, aux = _moe_group(xb, params, cfg, cap)
        return None, (y, aux)

    _, (yg, auxg) = jax.lax.scan(
        body, None, xg,
        unroll=n_groups if flags.UNROLL_FOR_COST_ANALYSIS else 1)
    return yg.reshape(T, -1), jnp.mean(auxg)


def _tokens_shardable(n_tokens: int) -> bool:
    mesh = shd.get_mesh()
    if mesh is None:
        return False
    baxes = shd.batch_axes(mesh)
    if not baxes:
        return False
    import numpy as np
    dp = int(np.prod([mesh.shape[a] for a in baxes]))
    return n_tokens % dp == 0 and n_tokens // dp >= 1


def moe_ffn(x, params, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux).  Chooses sharded vs local path."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    if not _tokens_shardable(B * S):
        y, aux = moe_ffn_local(x2d, params, cfg)
        return y.reshape(B, S, D), aux

    mesh = shd.get_mesh()
    baxes = shd.batch_axes(mesh)
    import numpy as np
    dp = int(np.prod([mesh.shape[a] for a in baxes]))
    t_loc = (B * S) // dp
    cap = capacity_for(t_loc, cfg)
    mode = cfg.moe.sharding_mode
    model_in_mesh = "model" in mesh.axis_names

    if mode == "expert" and model_in_mesh:
        w_spec_in = P("model", None, None)
        w_spec_out = P("model", None, None)
    else:
        w_spec_in = P(None, None, "model")
        w_spec_out = P(None, "model", None)

    def body(x_loc, router_w, w_in, w_gate, w_out):
        p_loc = {"router": router_w, "w_in": w_in, "w_gate": w_gate,
                 "w_out": w_out}
        if mode == "expert" and model_in_mesh:
            # Experts sharded: dispatch computed redundantly per model rank,
            # each rank runs only its expert slice, psum combines.
            # (Ungrouped: used for decode-scale token counts; the tensor
            # path below is the grouped production path for training.)
            top_idx, top_w, aux = route(x_loc, router_w, cfg)
            dispatch, combine = _dispatch_combine(
                top_idx, top_w, x_loc.shape[0], cap, cfg)
            e_loc = w_in.shape[0]
            midx = jax.lax.axis_index("model")
            # local slice of the (T, E, C) tensors along E
            d_loc = jax.lax.dynamic_slice_in_dim(dispatch, midx * e_loc,
                                                 e_loc, axis=1)
            c_loc = jax.lax.dynamic_slice_in_dim(combine, midx * e_loc,
                                                 e_loc, axis=1)
            inp = jnp.einsum("tec,td->ecd", d_loc,
                             x_loc.astype(jnp.float32)).astype(cfg.jnp_dtype)
            out = _expert_ffn(inp, p_loc, cfg)
            y = jnp.einsum("tec,ecd->td", c_loc, out.astype(jnp.float32))
            y = jax.lax.psum(y, "model")
        else:
            # Tensor mode: every rank has all experts with an ff slice;
            # w_out partial sums -> psum over model.  capacity=None: the
            # grouped local path computes per-GROUP capacity (passing the
            # full-shard capacity here would inflate every group's expert
            # buffers ~T_loc/GROUP-fold — caught by the roofline's
            # model_flops_ratio during the dry-run sweep).
            y, aux = moe_ffn_local(x_loc, p_loc, cfg, capacity=None)
            if model_in_mesh:
                y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, baxes)
        if model_in_mesh:
            aux = jax.lax.pmean(aux, "model")
        return y.astype(x_loc.dtype), aux

    # check_vma=False: the unreduced-psum replication check rejects the body
    y2d, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(baxes, None), P(None, None), w_spec_in, w_spec_in,
                  w_spec_out),
        out_specs=(P(baxes, None), P()),
        check_vma=False,
    )(x2d, params["router"], params["w_in"], params["w_gate"],
      params["w_out"])
    return y2d.reshape(B, S, D), aux
