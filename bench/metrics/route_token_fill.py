"""Share of the route passes' token slots that hold a token: non-pad
ids over bucket x max_len, summed over the window's ``ocl.route_pass``
spans of token levels."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_spans", Path(__file__).with_name("_spans.py"))
_spans = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_spans)


def read(ctx):
    return _spans.token_fill(ctx)
