"""Device ms per tick in the expert: the XLA programs of its forward, as
its kind's adapter names them (``PROGRAMS`` of
``bench/kinds/<kind>_program.py``)."""
import importlib.util
from pathlib import Path

import kinds

_spec = importlib.util.spec_from_file_location(
    "bench_metric_lib", Path(__file__).with_name("_lib.py"))
_lib = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lib)


def read(ctx):
    kind = ctx["cfg"]["cascade"]["expert"]["kind"]
    return _lib.module_ms_per_tick(ctx, kinds.program(kind).PROGRAMS)
