"""Tiny copies of the cells for CPU tests: a cell's own configuration,
traffic mix, driver, regime and limits, at widths a test run can hold."""
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

TINY = {"tinytf": dict(vocab=256, max_len=32, d_model=32, n_heads=2,
                       n_layers=1, d_ff=64),
        "expert": dict(vocab=256, max_len=32, d_model=32, n_heads=2,
                       n_layers=1, d_ff=64)}

# name -> (configuration file, traffic mix), as in BENCHMARK.json
CELLS = {"bert-learn-s64": ("bench/configs/ocl-bert-base.json", "learn-s64")}


def tiny_cell(name: str) -> dict:
    """The cell cut to CPU test size."""
    import run
    c = run.cell_from_files(name, *CELLS[name])
    casc = c["cfg"]["cascade"]
    for k, v in TINY.items():
        if k in casc:
            casc[k].update(v)
    for lv in casc["levels"]:
        lv["spec"] = casc.get(lv["kind"])
    c["mix"]["lanes"] = 8
    c["mix"]["corpus"].update(n_docs=512, mean_len=20)
    return c


@pytest.fixture(autouse=True, scope="session")
def _cpu_cache(tmp_path_factory):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
