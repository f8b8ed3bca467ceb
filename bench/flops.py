"""Operations and bytes the algorithm needs, computed from shapes.

The per-level forward counts are those of the repo's cost model
(``metrics/costs.py``), kept here so that no later change to the program
moves the yardstick.  A training step counts three forwards (forward,
and the backward's two products).
"""
from __future__ import annotations

import numpy as np


def lr_flops(n_features: int, n_classes: int) -> float:
    """One logistic-regression forward, per item."""
    return 2.0 * n_features * n_classes


def tinytf_flops(s: dict, n_classes: int) -> float:
    """One bidirectional ``tinytf`` encoder forward, per item."""
    L, d, f = s["max_len"], s["d_model"], s["d_ff"]
    per_layer = 8.0 * L * d * d + 4.0 * L * L * d + 4.0 * L * d * f
    return per_layer * s["n_layers"] + 2.0 * L * d + 2.0 * d * n_classes


def level_flops(level: dict, casc: dict) -> float:
    """Forward FLOPs of one item at ``level``."""
    C = casc["n_classes"]
    k = level["kind"]
    if k == "lr":
        return lr_flops(casc["n_features"], C)
    return {"tinytf": tinytf_flops}[k](level["spec"], C)


def window_flops(casc: dict, tick_levels, tick_called) -> float:
    """The FLOPs the window's ticks needed: every level an item reached
    (calibration forwards of called items included, padding excluded),
    the expert on called items, and one student step per level per tick
    that called the expert."""
    levels = casc["levels"]
    fwd = np.array([level_flops(lv, casc) for lv in levels])
    step = sum(3.0 * f * min(lv["batch_size"], lv["cache_size"])
               for f, lv in zip(fwd, levels))
    expert = (tinytf_flops(casc["expert"], casc["n_classes"])
              if "expert" in casc else 0.0)
    total = 0.0
    for lv_out, called in zip(tick_levels, tick_called):
        lv_out = np.asarray(lv_out)
        called = np.asarray(called, bool)
        for i, f in enumerate(fwd):
            total += f * float(np.sum(called | (lv_out >= i)))
        k = int(called.sum())
        total += expert * k + (step if k else 0.0)
    return total
