"""Logical-axis -> mesh-axis sharding rules.

The production mesh is ``(pod, data, model)`` (multi-pod) or ``(data, model)``
(single pod).  Rules:

* batch-like dims            -> ('pod', 'data')   [whatever subset exists]
* attention heads / d_ff / experts' ff / mamba d_inner / vocab -> 'model'
* everything else replicated.

A module-level "current mesh" avoids threading the mesh through every model
function; ``constrain`` is a no-op when no mesh is set (single-device tests)
or when a dim is not divisible by the axis size (e.g. batch=1 long_500k).
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CURRENT_MESH: Optional[Mesh] = None

# Sequence parallelism (beyond-paper, §Perf): shard the sequence dim of
# inter-block activations over 'model' in addition to batch over
# (pod,data).  GSPMD then turns the tensor-parallel all-reduces into
# reduce-scatter/all-gather pairs and the stored scan carries shrink by
# the model-axis size (Megatron-SP pattern, via sharding constraints).
SEQ_PARALLEL = False


def set_seq_parallel(v: bool) -> None:
    """Toggle the Megatron-SP activation-sharding pattern globally."""
    global SEQ_PARALLEL
    SEQ_PARALLEL = v


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Install ``mesh`` as the process-wide default device mesh."""
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    """The process-wide default device mesh, if one is installed."""
    return _CURRENT_MESH


def batch_axes(mesh: Optional[Mesh] = None):
    """The mesh axes a batch dim shards over ('pod','data' subset)."""
    mesh = mesh or _CURRENT_MESH
    if mesh is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return axes if axes else None


# ---------------------------------------------------------------------------
# Lane sharding (batched cascade engine)
# ---------------------------------------------------------------------------
# The batched cascade engine's per-lane state is lane-major: feature
# batches, deferral probs, alive/called masks, expert labels, per-lane
# weights.  Lanes shard over the batch-like mesh axes ('pod','data');
# the shared cascade state (student params, deferral MLPs, optimizer
# state, demonstration ring buffers) is replicated — it is one cascade
# serving S lanes, not S cascades.

def lane_count(mesh: Mesh) -> int:
    """Number of devices the lane dim shards over ('pod' x 'data')."""
    return _axis_size(mesh, batch_axes(mesh))


def lane_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding dim 0 over the lane axes."""
    axes = batch_axes(mesh)
    return P(axes) if axes else P()


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding placing dim 0 on the lane ('pod','data') axes."""
    return NamedSharding(mesh, lane_spec(mesh))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding replicating a value on every device of ``mesh``."""
    return NamedSharding(mesh, P())


def put_lanes(x, mesh: Mesh) -> jax.Array:
    """Place a lane-major host array with dim 0 sharded over the lane
    axes; falls back to replication when the dim does not divide (e.g. a
    partial final tick), mirroring ``constrain``'s divisibility rule."""
    x = np.asarray(x)
    if x.ndim and _fits(mesh, x.shape[0], batch_axes(mesh)):
        return jax.device_put(x, lane_sharding(mesh))
    return jax.device_put(x, replicated_sharding(mesh))


def put_replicated(x, mesh: Mesh) -> jax.Array:
    """Place ``x`` replicated over every device of ``mesh``."""
    return jax.device_put(x, replicated_sharding(mesh))


# ---------------------------------------------------------------------------
# In-flight route buffers (pipelined batched engine)
# ---------------------------------------------------------------------------
# The pipelined route mode (core/batched.py ``pipeline_depth``) keeps a
# P-deep ring of dispatched-but-unresolved ticks.  Each in-flight tick
# pins one padded lane feature buffer (the route pass input) and one
# (probs, dprob) output pair on the device until host routing resolves
# it.  Two annotations keep that ring cheap:

def jit_route_pass(fn, mesh: Optional[Mesh] = None):
    """Jit a per-level route pass ``fn(params, dparams, xb)``.

    ``xb`` is the padded lane-major feature buffer built fresh for each
    dispatch and never read again by the host.  With a mesh (where
    ``put_lanes`` has committed it to devices) it is donated, so a
    pipeline holding P ticks in flight pins only the route *outputs*
    instead of also keeping P dead input buffers alive.  Without a mesh
    the inputs may be uncommitted host-local arrays — donation would be
    ignored with a warning — so the plain jit is returned.
    """
    if mesh is None:
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=(2,))


def jit_cache_scatter(fn, mesh: Optional[Mesh] = None):
    """Jit the demonstration ring-buffer scatter ``fn(cx, cy, feats, y,
    called, ptr)`` with the ring buffers donated.

    The buffers mutate in place instead of copying — and with a mesh the
    outputs are pinned replicated so the donated buffers keep the same
    placement call after call.  Placement stability matters doubly in
    per-lane commit mode (core/batched.py ``per_lane=True``), where the
    scatter runs once per committed *lane* rather than once per tick:
    any placement drift would break the donation chain on every lane.
    """
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0, 1))
    return jax.jit(fn, donate_argnums=(0, 1),
                   out_shardings=replicated_sharding(mesh))


def jit_tick_update(fn, mesh: Optional[Mesh] = None):
    """Jit a tick's whole update pass ``fn(cx, cy, state, bufs, layout)``
    with the ring buffers and every level's learned state donated.

    One program per committed tick writes the ring buffers and steps the
    students and deferral gates in place, so the host dispatches one call
    and the device allocates no second copy of the state.  ``layout`` is
    static (the shapes of the tick's packed host inputs ``bufs``).  With a
    mesh the outputs are pinned replicated, like ``jit_cache_scatter``'s,
    so each call's donated inputs keep the placement the last call gave
    them.
    """
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0, 1, 2), static_argnums=(4,))
    return jax.jit(fn, donate_argnums=(0, 1, 2), static_argnums=(4,),
                   out_shardings=replicated_sharding(mesh))


def host_prefetch(arrays) -> None:
    """Start async device->host copies for ``arrays`` (non-blocking).

    The pipelined route ring calls this right after dispatching a tick's
    forwards: the D2H transfer of the in-flight ``(probs, dprob)`` pair
    is enqueued behind their producing computation, so it overlaps the
    next ticks' device compute and the eventual ``np.asarray`` at host
    resolution is a wait on a transfer already done, not a round trip.
    """
    for a in arrays:
        copy = getattr(a, "copy_to_host_async", None)
        if copy is not None:
            copy()


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fits(mesh: Mesh, dim: int, axes) -> bool:
    size = _axis_size(mesh, axes)
    return size > 0 and dim % size == 0


def constrain(x, spec: Sequence) -> jax.Array:
    """with_sharding_constraint against the current mesh.

    ``spec`` entries are mesh-axis names (or tuples of them) per dim, or None.
    Dims whose size is not divisible by the axis size are silently
    replicated instead, so the same model code serves batch=256 training and
    batch=1 long-context decode.
    """
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    cleaned = []
    for dim, axes in zip(x.shape, spec):
        if axes is None:
            cleaned.append(None)
            continue
        present = tuple(a for a in (axes if isinstance(axes, tuple) else (axes,))
                        if a in mesh.axis_names)
        if present and _fits(mesh, dim, present):
            cleaned.append(present if len(present) > 1 else present[0])
        else:
            cleaned.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*cleaned)))


def constrain_tokens(x) -> jax.Array:
    """Shard (B, S, ...) activations: batch over (pod,data); if batch cannot
    shard (batch=1 long-context), shard the sequence dim instead.  With
    SEQ_PARALLEL also shard the sequence dim over 'model'."""
    mesh = _CURRENT_MESH
    if mesh is None:
        return x
    baxes = batch_axes(mesh)
    seq_ax = "model" if (SEQ_PARALLEL and x.ndim >= 2
                         and "model" in mesh.axis_names
                         and _fits(mesh, x.shape[1], ("model",))) else None
    if baxes and _fits(mesh, x.shape[0], baxes):
        return constrain(x, (baxes, seq_ax) + (None,) * (x.ndim - 2))
    if x.ndim >= 2 and baxes and _fits(mesh, x.shape[1], baxes):
        return constrain(x, (None, baxes) + (None,) * (x.ndim - 2))
    return x


# ---------------------------------------------------------------------------
# Parameter partition specs (path-based rules)
# ---------------------------------------------------------------------------
# Each rule: (path regex, spec builder taking ndim -> tuple). The leading
# n_periods stacking dim (present on block params) is always replicated.
# Specs below are for the *unstacked* suffix dims.

_RULES = [
    # embeddings / lm head: shard vocab over model
    (r"embed/table$",        lambda nd: ("model", None)),
    (r"lm_head/w$",          lambda nd: (None, "model")),
    # attention projections
    (r"(attn|self_attn|cross_attn)/wq$", lambda nd: (None, "model")),
    (r"(attn|self_attn|cross_attn)/wk$", lambda nd: (None, "model")),
    (r"(attn|self_attn|cross_attn)/wv$", lambda nd: (None, "model")),
    (r"(attn|self_attn|cross_attn)/wo$", lambda nd: ("model", None)),
    # dense mlp
    (r"mlp/w_gate$",         lambda nd: (None, "model")),
    (r"mlp/w_in$",           lambda nd: (None, "model")),
    (r"mlp/w_out$",          lambda nd: ("model", None)),
    # moe: tensor mode shards expert ff dim; router replicated
    (r"moe/w_gate$",         lambda nd: (None, None, "model")),
    (r"moe/w_in$",           lambda nd: (None, None, "model")),
    (r"moe/w_out$",          lambda nd: (None, "model", None)),
    (r"moe/router$",         lambda nd: (None, None)),
    # mamba: shard d_inner / heads over model
    (r"mamba/in_proj$",      lambda nd: (None, "model")),
    (r"mamba/conv_w$",       lambda nd: (None, "model")),
    (r"mamba/conv_b$",       lambda nd: ("model",)),
    (r"mamba/A_log$",        lambda nd: ("model",)),
    (r"mamba/D$",            lambda nd: ("model",)),
    (r"mamba/dt_bias$",      lambda nd: ("model",)),
    (r"mamba/gate_norm$",    lambda nd: ("model",)),
    (r"mamba/out_proj$",     lambda nd: ("model", None)),
]

_EXPERT_MODE_RULES = [
    # expert-parallel: shard the expert dim instead of ff
    (r"moe/w_gate$",         lambda nd: ("model", None, None)),
    (r"moe/w_in$",           lambda nd: ("model", None, None)),
    (r"moe/w_out$",          lambda nd: ("model", None, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_path(path_str: str, ndim: int, stacked: bool,
                  moe_mode: str = "tensor") -> P:
    """PartitionSpec for a parameter path via the placement rule table."""
    rules = list(_RULES)
    if moe_mode == "expert":
        rules = _EXPERT_MODE_RULES + rules
    for pat, builder in rules:
        if re.search(pat, path_str):
            suffix = builder(ndim)
            if stacked:
                # leading n_periods dim replicated; pad/trim to ndim
                suffix = (None,) + tuple(suffix)
            suffix = tuple(suffix)[:ndim]
            suffix = suffix + (None,) * (ndim - len(suffix))
            return P(*suffix)
    return P(*([None] * ndim))


def param_pspecs(params, moe_mode: str = "tensor"):
    """Tree of PartitionSpec matching ``params`` (shapes or arrays)."""
    def one(path, leaf):
        ps = _path_str(path)
        ndim = len(leaf.shape)
        stacked = "/blocks/" in ("/" + ps + "/") or ps.startswith("blocks/")
        return spec_for_path(ps, ndim, stacked, moe_mode)
    return jax.tree_util.tree_map_with_path(one, params)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    """Bind one PartitionSpec to ``mesh`` as a NamedSharding."""
    return NamedSharding(mesh, spec)


def tree_named_shardings(mesh: Mesh, spec_tree):
    """Map a PartitionSpec tree to NamedShardings on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
