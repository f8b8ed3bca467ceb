"""Model FLOPs the window's ticks needed (``flops.window_flops``) over
the window's wall time and the chip's bf16 peak."""
import flops


def read(ctx):
    w = ctx["window"]
    if not w["tick_levels"] or not ctx["peak"]:
        return None
    f = flops.window_flops(ctx["cfg"]["cascade"], w["tick_levels"],
                           w["tick_called"])
    return 100.0 * f / (w["seconds"] * ctx["peak"]["bf16_flops"])
