"""Sharding rules and mesh placement helpers (see ``specs`` module)."""
from repro.sharding.specs import (
    batch_axes, constrain, constrain_tokens, get_mesh, host_prefetch,
    jit_cache_scatter, jit_route_pass, jit_tick_update, lane_count,
    lane_sharding, lane_spec, named_sharding,
    param_pspecs, put_lanes, put_replicated, replicated_sharding, set_mesh,
    tree_named_shardings,
)

__all__ = [
    "set_mesh", "get_mesh", "constrain", "constrain_tokens", "batch_axes",
    "lane_count", "lane_spec", "lane_sharding", "replicated_sharding",
    "put_lanes", "put_replicated", "jit_route_pass", "jit_cache_scatter",
    "jit_tick_update", "host_prefetch",
    "param_pspecs", "named_sharding", "tree_named_shardings",
]
