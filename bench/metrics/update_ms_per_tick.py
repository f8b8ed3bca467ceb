"""Device ms per tick in the update pass: the ring-buffer scatter and the
student and deferral steps (``core/batched.py`` ``_commit``)."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_lib", Path(__file__).with_name("_lib.py"))
_lib = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lib)


def read(ctx):
    return _lib.module_ms_per_tick(
        ctx, {"scatter", "student_step", "deferral_step"})
