"""Kinds: what each level and the expert is comes from files found by the
configuration's ``kind`` (``bench/kinds/``), and moving it there moved no
number.

- The golden file (``record_golden.py``) holds the reference side's
  numbers at the tiny ``bert-learn-s64``, and the weight tree's shapes and
  the FLOP counts at the cell's own size: the tree reproduces each bit
  for bit.
- A kind that is new files in a directory of its own serves as the
  cell's expert with no edit to the harness, and a run with it comes
  out correct.
- A kind with no file fails at set-up, naming the file.
"""
import ast
import json
import re
from pathlib import Path

import pytest

import conftest
import record_golden

BENCH = Path(__file__).resolve().parents[1]
CELL = "bert-learn-s64"
SEED = 2 ** 31 + 6262

FIXTURE = '''"""A kind for the harness's tests: the tinytf encoder under another
name, with test sizes of its own."""
import kinds

_tf = kinds.reference("tinytf")
OPTIMIZER = _tf.OPTIMIZER
TINY = dict(vocab=128, max_len=24, d_model=16, n_heads=2, n_layers=1,
            d_ff=32)
weights, logits, featurize = _tf.weights, _tf.logits, _tf.featurize
row, flops, balance = _tf.row, _tf.flops, _tf.balance
'''

FIXTURE_PROGRAM = '''"""The program's tinytf expert, serving the test kind."""
import kinds

_tf = kinds.program("tinytf")
PROGRAMS = {PROGRAMS}
BUILT = []


def build(params, spec, n_classes, cost):
    BUILT.append(spec)
    return _tf.build(params, spec, n_classes, cost)
'''


@pytest.fixture(scope="module")
def golden():
    want = json.loads(record_golden.OUT.read_text())
    return want, record_golden.golden()


@pytest.mark.parametrize("key", ["weights", "balanced_expert", "rows",
                                 "probs", "expert_logits", "replay",
                                 "window_flops", "full"])
def test_reference_side_matches_golden(golden, key):
    want, got = golden
    assert json.loads(json.dumps(got[key])) == want[key]


def _fixture_kind(tmp_path, monkeypatch, programs="_tf.PROGRAMS"):
    import kinds
    (tmp_path / "fixture_tf.py").write_text(FIXTURE)
    (tmp_path / "fixture_tf_program.py").write_text(
        FIXTURE_PROGRAM.replace("{PROGRAMS}", programs))
    monkeypatch.setattr(kinds, "DIRS", [tmp_path, *kinds.DIRS])
    return kinds


def _config_with(tmp_path, where: str, kind: str) -> str:
    cfg = json.loads((BENCH.parent / conftest.CELLS[CELL][0]).read_text())
    casc = cfg["cascade"]
    (casc["expert"] if where == "expert" else casc["levels"][1])["kind"] = kind
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    return str(path)


def test_new_expert_kind_is_new_files(tmp_path, monkeypatch):
    import run
    kinds = _fixture_kind(tmp_path, monkeypatch)
    c = conftest.tiny(run.cell_from_files(
        CELL, _config_with(tmp_path, "expert", "fixture_tf"),
        conftest.CELLS[CELL][1]))
    expert = c["cfg"]["cascade"]["expert"]
    assert expert["d_model"] == 16 and expert["max_len"] == 24
    out = run.run_cell(c, SEED, 1.5, False, check_device=False)
    assert out["correct"], out["checks"]
    assert kinds.program("fixture_tf").BUILT == [expert]


@pytest.mark.parametrize("where", ["expert", "level"])
def test_missing_kind_fails_at_setup(tmp_path, where):
    import run
    with pytest.raises(run.SetupError, match=r"no_such_kind(_program)?\.py"):
        run.cell_from_files(CELL, _config_with(tmp_path, where,
                                               "no_such_kind"),
                            conftest.CELLS[CELL][1])


def test_expert_ms_reads_the_adapters_programs(tmp_path, monkeypatch):
    import run
    read = run.load_reader("expert_ms_per_tick")
    modules = {"_lambda": 0.004, "_argmax": 0.001, "fixture_fwd": 0.03,
               "route_pass": 2.0}
    ctx = {"trace": {"modules": modules}, "window": {"ticks": 10},
           "cfg": {"cascade": {"expert": {"kind": "tinytf"}}}}
    assert read(ctx) == pytest.approx(0.5)
    _fixture_kind(tmp_path, monkeypatch, programs='{"fixture_fwd"}')
    ctx["cfg"]["cascade"]["expert"]["kind"] = "fixture_tf"
    assert read(ctx) == pytest.approx(3.0)


def test_kinds_live_under_kinds():
    """No kind is named outside ``bench/kinds/``, and the reference side
    imports nothing of the program."""
    files = [*BENCH.glob("*.py"), *BENCH.glob("metrics/*.py"),
             BENCH / "tests" / "conftest.py"]
    for f in files:
        text = f.read_text()
        assert '"tinytf"' not in text and "'tinytf'" not in text, f
        assert not re.search(r"""kind"?'?\]?\s*==\s*["']lr["']""", text), f
    for f in (BENCH / "kinds").glob("*.py"):
        if f.name.endswith("_program.py"):
            continue
        for node in ast.walk(ast.parse(f.read_text())):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""] if isinstance(node, ast.ImportFrom)
                     else [])
            assert not any(n.split(".")[0] == "repro" for n in names), f
