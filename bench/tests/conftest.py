"""Tiny copies of the cells for CPU tests: a cell's own configuration,
traffic mix, driver, regime and limits (``BENCHMARK.json``), at the sizes
each kind's ``TINY`` gives and with a short mix."""
import json
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

# name -> (configuration file, traffic mix), from BENCHMARK.json
_SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
_FILES = {c["name"]: c["file"] for c in _SPEC["configs"]}
CELLS = {w["name"]: (_FILES[w["config"]], w["traffic"])
         for w in _SPEC["workloads"]}


def tiny(c: dict) -> dict:
    """A cell (``run.cell_from_files``) cut to CPU test size."""
    import kinds
    casc = c["cfg"]["cascade"]
    for lv in casc["levels"]:
        if lv["spec"] is not None:
            lv["spec"].update(kinds.reference(lv["kind"]).TINY)
    casc["expert"].update(kinds.reference(casc["expert"]["kind"]).TINY)
    c["mix"]["lanes"] = 8
    c["mix"]["corpus"].update(n_docs=512, mean_len=20)
    return c


def tiny_cell(name: str) -> dict:
    """The cell ``name`` cut to CPU test size."""
    import run
    return tiny(run.cell_from_files(name, *CELLS[name]))


@pytest.fixture(autouse=True, scope="session")
def _cpu_cache(tmp_path_factory):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("jax_cache"))
