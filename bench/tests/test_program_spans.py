"""The program's host spans in a trace (``spans.py``) and the metrics that
read them, on hand-built traces and on a recorded one."""
import importlib.util
import json
from pathlib import Path

import pytest

import spans

MS = 1_000_000
DATA = Path(__file__).with_name("data")
RECORDED = DATA / "small_spans.xplane.pb"
METRICS = ("idle_draws_ms_per_tick", "idle_featurize_ms_per_tick",
           "idle_wait_ms_per_tick", "idle_commit_ms_per_tick",
           "route_token_fill")


def _reader(name):
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _tick():
    """One 100 ms tick: the program's spans nested in ``bench.tick`` on
    the main thread, the expert's featurizing on a worker thread, and
    three device operations."""
    host = [
        (0, 100 * MS, "bench.window", {}),
        (0, 100 * MS, "bench.tick", {}),
        (0, 10 * MS, "ocl.route_dispatch", {"tick": 7, "lanes": 64}),
        (0, 6 * MS, "ocl.draws", {"tick": 7, "lanes": 64}),
        (10 * MS, 100 * MS, "ocl.route_resolve",
         {"tick": 7, "lanes": 64, "called": 64}),
        (30 * MS, 32 * MS, "ocl.route_pass",
         {"tick": 7, "level": 1, "rows": 40, "bucket": 64, "calib": 0,
          "tokens": 9000, "token_slots": 64 * 512}),
        (40 * MS, 50 * MS, "ocl.wait", {"tick": 7, "level": 1}),
        (50 * MS, 60 * MS, "ocl.wait", {"tick": 7, "level": 1}),
        (70 * MS, 71 * MS, "ocl.route_pass",
         {"tick": 7, "level": 1, "rows": 3, "bucket": 8, "calib": 1,
          "tokens": 700, "token_slots": 8 * 512}),
        (80 * MS, 84 * MS, "ocl.featurize", {"level": "expert", "rows": 64}),
        (200 * MS, 201 * MS, "ocl.route_pass",             # past the end
         {"tick": 8, "level": 1, "rows": 8, "bucket": 8, "calib": 0,
          "tokens": 1, "token_slots": 8 * 512})]
    planes = [("/host:CPU", [("python", [(n, s, e - s)
                                         for s, e, n, _ in host])]),
              ("/device:TPU:0", [
                  ("XLA Modules", [("jit_route_pass(1)", 10 * MS, 30 * MS)]),
                  ("XLA Ops", [("fusion.1", 10 * MS, 30 * MS),
                               ("fusion.2", 60 * MS, 10 * MS),
                               ("fusion.3", 88 * MS, 7 * MS)])])]
    return planes, host


def test_program_span_inside_bench_tick_names_the_gap():
    gaps = spans.reduce(*_tick())["idle_gaps"]
    assert [n for n, _ in gaps] == [
        "ocl.wait",                      # 40..60 ms: mid at 50
        "ocl.route_resolve",             # 70..88 ms: mid at 79
        "ocl.draws",                     # 0..10 ms: mid at 5
        "ocl.route_resolve"]             # 95..100 ms
    assert [s for _, s in gaps] == pytest.approx([0.02, 0.018, 0.01, 0.005])


def test_idle_stretch_split_exactly_across_sibling_spans():
    red = spans.reduce(*_tick())
    by = red["idle_by_span"]
    # 0..10 ms: the draws 0..6, then the rest of stage A
    assert by["ocl.draws"] == pytest.approx(0.006)
    assert by["ocl.route_dispatch"] == pytest.approx(0.004)
    # 40..60 ms: two sibling waits, 10 ms each
    assert by["ocl.wait"] == pytest.approx(0.020)
    # 70..88 ms: a calibration pass, the expert's featurizing on its
    # worker thread (the shortest span over 80..84), stage B's own walk
    assert by["ocl.route_pass"] == pytest.approx(0.001)
    assert by["ocl.featurize"] == pytest.approx(0.004)
    assert by["ocl.route_resolve"] == pytest.approx(0.009 + 0.004 + 0.005)
    assert "bench.tick" not in by and "host:none" not in by
    assert sum(by.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    assert red["busy_s"] == pytest.approx(0.047)


def test_span_arguments_reach_program_spans_and_readers():
    red = spans.reduce(*_tick())
    names = [n for n, *_ in red["program_spans"]]
    assert names[:3] == ["ocl.route_dispatch", "ocl.draws",
                         "ocl.route_resolve"]
    passes = [(s, e, a) for n, s, e, a in red["program_spans"]
              if n == "ocl.route_pass"]
    assert len(passes) == 2                  # the third starts past the end
    assert passes[0][:2] == pytest.approx((0.030, 0.032))
    assert passes[0][2] == {"tick": 7, "level": 1, "rows": 40,
                            "bucket": 64, "calib": 0, "tokens": 9000,
                            "token_slots": 64 * 512}
    ctx = {"trace": {"window_s": red["window_s"]}, "window": {"ticks": 1},
           "spans": red}
    assert _reader("route_token_fill")(ctx) == pytest.approx(
        100 * 9700 / (72 * 512))
    assert _reader("idle_wait_ms_per_tick")(ctx) == pytest.approx(20.0)
    assert _reader("idle_draws_ms_per_tick")(ctx) == pytest.approx(6.0)
    assert _reader("idle_featurize_ms_per_tick")(ctx) == pytest.approx(4.0)
    assert _reader("idle_commit_ms_per_tick")(ctx) == 0.0


def test_readers_read_nothing_without_program_spans():
    planes, host = _tick()
    bench_only = [x for x in host if x[2].startswith("bench.")]
    red = spans.reduce(planes, bench_only)
    assert red["program_spans"] == []
    assert red["idle_by_span"] == pytest.approx(
        {"bench.tick": red["window_s"] - red["busy_s"]})
    for ctx in ({"trace": {"window_s": 0.1}, "window": {"ticks": 1},
                 "spans": red},
                {"trace": None, "window": {"ticks": 1}}):
        for name in METRICS:
            assert _reader(name)(ctx) is None


def test_traced_run_checks_the_window(tmp_path):
    assert spans.traced_run(None, tmp_path) is None
    assert spans.traced_run({"window_s": 1.0}, tmp_path) is None
    if RECORDED.exists():
        d = tmp_path / ".bench_trace" / "cell"
        d.mkdir(parents=True)
        (d / "t.xplane.pb").write_bytes(RECORDED.read_bytes())
        red = spans.reduce(*spans.read(RECORDED))
        assert spans.traced_run({"window_s": red["window_s"]},
                                tmp_path)["program_spans"]
        assert spans.traced_run({"window_s": red["window_s"] + 1},
                                tmp_path) is None


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace_with_program_spans():
    """A test-size engine's three ticks on the chip
    (``record_spans_trace.py``): the token fill equals the host's count
    of its padded batches, and the idle split adds up to the idle time
    that ``xplane`` finds."""
    import xplane
    red = spans.reduce(*spans.read(RECORDED))
    host = json.loads(RECORDED.with_name(RECORDED.name + ".host.json")
                      .read_text())
    ctx = {"trace": xplane.reduce_trace(RECORDED), "window": {"ticks": 3},
           "spans": red}
    assert red["window_s"] == ctx["trace"]["window_s"]
    assert red["busy_s"] == pytest.approx(ctx["trace"]["busy_s"], abs=1e-12)
    assert _reader("route_token_fill")(ctx) == pytest.approx(
        100 * host["tokens"] / host["token_slots"], abs=1e-12)
    by = red["idle_by_span"]
    assert sum(by.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    named = sum(v for k, v in by.items() if k.startswith("ocl."))
    assert named >= 0.9 * sum(by.values())
    assert {"ocl.route_dispatch", "ocl.route_resolve", "ocl.wait"} <= set(by)
    ticks = {a["tick"] for n, _, _, a in red["program_spans"]
             if n == "ocl.route_resolve"}
    assert len(ticks) == 3
