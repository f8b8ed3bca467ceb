"""Device-idle ms per tick in the host side of the update pass
(``ocl.commit`` and its ``ocl.sample`` and ``ocl.update`` children)."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_spans", Path(__file__).with_name("_spans.py"))
_spans = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_spans)


def read(ctx):
    return _spans.idle_ms_per_tick(
        ctx, {"ocl.commit", "ocl.sample", "ocl.update"})
