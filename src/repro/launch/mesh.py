"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A Mesh with the given axis sizes/names, every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` and the engine's lane gathers refuse;
    the sharding rules here are written for the compiler-propagated
    (``Auto``) mode, so every mesh of the repo is built through this.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist, as a (data, model) mesh with model = 1."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def parse_mesh_spec(spec: str):
    """``"data=8"`` / ``"pod=2,data=4"`` -> a Mesh with those axes.

    The CLI knob behind ``serve.py --mesh``: axis sizes must multiply to
    at most the available device count (use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for virtual
    CPU devices).  Returns None for an empty/absent spec.
    """
    if not spec:
        return None
    shape, axes = [], []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        if not name or not size.strip().isdigit() or int(size) < 1:
            raise ValueError(f"bad mesh spec {spec!r}: expected "
                             f"'axis=N[,axis=N...]' with N >= 1, "
                             f"got {part!r}")
        axes.append(name)
        shape.append(int(size))
    return make_mesh(tuple(shape), tuple(axes))
