"""Shared arithmetic of the readers of the program's host spans
(``ocl.*``, ``core/batched.py``; ``bench/spans.py`` reduces them); not a
metric itself.  Each returns None when the traced run holds no program
span, as a trace of a program without them does."""
from pathlib import Path

import spans

ROOT = Path(__file__).resolve().parents[2]


def reduction(ctx) -> "dict | None":
    """``spans.traced_run`` of this run, made once and kept in ``ctx``
    for the other readers of the same run."""
    if "spans" not in ctx:
        ctx["spans"] = spans.traced_run(ctx["trace"], ROOT)
    return ctx["spans"]


def idle_ms_per_tick(ctx, names) -> "float | None":
    """Device-idle milliseconds per tick whose innermost host span is one
    of ``names`` (``idle_by_span``, averaged over chips)."""
    red = reduction(ctx)
    ticks = ctx["window"]["ticks"]
    if not red or not red["program_spans"] or not ticks:
        return None
    idle = sum(v for k, v in red["idle_by_span"].items() if k in names)
    return idle / ticks * 1e3


def token_fill(ctx) -> "float | None":
    """100 x the non-pad token ids over the token slots of the window's
    route passes (``ocl.route_pass`` ``tokens`` / ``token_slots``)."""
    red = reduction(ctx)
    if not red:
        return None
    tok = slots = 0
    for name, _, _, args in red["program_spans"]:
        if name == "ocl.route_pass" and "token_slots" in args:
            tok += args["tokens"]
            slots += args["token_slots"]
    return 100.0 * tok / slots if slots else None
