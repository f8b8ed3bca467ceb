"""Operations the algorithm needs, computed from shapes.

Each kind's forward count is its file's ``flops`` (``bench/kinds/``),
those of the repo's cost model (``metrics/costs.py``) for the kinds it
has, kept with the benchmark so that no later change to the program
moves the yardstick.  A training step counts three forwards (forward,
and the backward's two products).
"""
from __future__ import annotations

import numpy as np

import kinds


def level_flops(level: dict, casc: dict) -> float:
    """Forward FLOPs of one item at ``level``."""
    return kinds.reference(level["kind"]).flops(
        level.get("spec"), casc["n_classes"], casc["n_features"])


def window_flops(casc: dict, tick_levels, tick_called) -> float:
    """The FLOPs the window's ticks needed: every level an item reached
    (calibration forwards of called items included, padding excluded),
    the expert on called items, and one student step per level per tick
    that called the expert."""
    levels = casc["levels"]
    fwd = np.array([level_flops(lv, casc) for lv in levels])
    step = sum(3.0 * f * min(lv["batch_size"], lv["cache_size"])
               for f, lv in zip(fwd, levels))
    e = casc.get("expert")
    expert = (kinds.reference(e["kind"]).flops(e, casc["n_classes"],
                                               casc["n_features"])
              if e else 0.0)
    total = 0.0
    for lv_out, called in zip(tick_levels, tick_called):
        lv_out = np.asarray(lv_out)
        called = np.asarray(called, bool)
        for i, f in enumerate(fwd):
            total += f * float(np.sum(called | (lv_out >= i)))
        k = int(called.sum())
        total += expert * k + (step if k else 0.0)
    return total
