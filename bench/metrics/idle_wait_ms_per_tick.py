"""Device-idle ms per tick while the host blocks on a device result
(``ocl.wait``: the round trip of each route, calibration and expert
sync)."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_spans", Path(__file__).with_name("_spans.py"))
_spans = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_spans)


def read(ctx):
    return _spans.idle_ms_per_tick(ctx, {"ocl.wait"})
