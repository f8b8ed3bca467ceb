"""Shared arithmetic of the metric readers (not a metric itself)."""


def module_ms_per_tick(ctx, names) -> "float | None":
    """Device milliseconds per tick of the XLA programs whose base name
    is one of ``names``, over the traced window; None if none ran."""
    red = ctx["trace"]
    ticks = ctx["window"]["ticks"]
    if not red or not ticks:
        return None
    t = sum(v for k, v in red["modules"].items() if k in names)
    return t / ticks * 1e3 if t > 0 else None
