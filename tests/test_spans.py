"""Host spans of the batched engine (``ocl.*``, core/batched.py): each
tick's phases on the profiler's host plane, with the tick and the counts
of their work as arguments, and no effect on what the engine computes."""
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from harness import make_setup, state_leaves
from repro.core import BatchedCascadeEngine, ModelExpert
from repro.models.students import TinyTFSpec, tinytf_init

S = 8
SPEC = TinyTFSpec(vocab=256, max_len=32, d_model=32, n_heads=2, n_layers=1,
                  d_ff=64, n_classes=2)


@pytest.fixture(scope="module")
def engine():
    """``engine(max_delay, per_lane)``: LR -> tinytf at test size, served
    by a ModelExpert, reset to tick 0.  One engine per configuration for
    the module, so that its programs compile once; beta0 0.5 so that a
    tick has walk forwards as well as calibration forwards."""
    stream, cfg = make_setup(3e-7, 8 * S)
    cfg = replace(cfg, tf_spec=SPEC, beta0=0.5)
    built = {}

    def get(max_delay=0, per_lane=False):
        key = (max_delay, per_lane)
        if key not in built:
            expert = ModelExpert(
                params=tinytf_init(jax.random.PRNGKey(1), SPEC), spec=SPEC)
            built[key] = BatchedCascadeEngine(
                cfg, expert, n_streams=S, max_delay=max_delay,
                per_lane=per_lane)
        eng = built[key]
        eng.reset()
        return stream, eng

    yield get
    for eng in built.values():
        eng.close()


def _ticks(eng, stream, n=3):
    """The next ``n`` ticks of the stream (tick-major, as ``run``
    serves it), then a flush."""
    outs = []
    for k in range(eng.t, eng.t + n):
        idxs = list(range(k * S, (k + 1) * S))
        outs.append(eng.process_tick(idxs, [stream.docs[i] for i in idxs]))
    eng.flush()
    return outs


def _traced(eng, stream, log_dir, n=3):
    """Run ``n`` ticks under the profiler; their outputs and the trace's
    ``ocl.*`` events as ``(name, start_ns, end_ns, args, thread)``."""
    _ticks(eng, stream, 1)            # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the spans, not every Python call
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        outs = _ticks(eng, stream, n)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ocl."):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats), (plane.name, li)))
    return outs, sorted(spans, key=lambda x: x[1])


def _inside(inner, outer):
    return (inner[4] == outer[4] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_tick_spans_nest_and_count_the_host_batches(tmp_path, engine,
                                                    monkeypatch):
    """Every tick has its two stage spans, the draws, featurizing and
    route passes nest in them, and each ``ocl.route_pass`` carries the
    rows, bucket and non-pad tokens of the batch the host padded."""
    stream, eng = engine()
    host = []                         # (level, rows, bucket, tokens)
    dispatch = eng._dispatch_level

    def recorded(i, fi, sel, t, calib=0):
        handles, xb = dispatch(i, fi, sel, t, calib)
        tok = (int(np.count_nonzero(xb))
               if np.issubdtype(xb.dtype, np.integer) else None)
        host.append((i, sel.size, eng._bucket(sel.size), tok))
        return handles, xb

    monkeypatch.setattr(eng, "_dispatch_level", recorded)
    outs, spans = _traced(eng, stream, tmp_path)
    host = host[-sum(1 for s in spans if s[0] == "ocl.route_pass"):]
    ticks = [int(o["tick"]) for o in outs]
    assert ticks == [2, 3, 4]
    for t in ticks:
        stages = [s for s in spans if s[3].get("tick") == t
                  and s[0] in ("ocl.route_dispatch", "ocl.route_resolve")]
        assert sorted(s[0] for s in stages) == ["ocl.route_dispatch",
                                                "ocl.route_resolve"]
        assert all(s[3]["lanes"] == S for s in stages)
        for s in spans:
            if s[3].get("tick") == t and s[0] in (
                    "ocl.draws", "ocl.featurize", "ocl.route_pass"):
                assert any(_inside(s, st) for st in stages), s
    assert {s[0] for s in spans} >= {"ocl.draws", "ocl.featurize",
                                     "ocl.wait", "ocl.expert",
                                     "ocl.commit", "ocl.sample",
                                     "ocl.update"}
    passes = [s for s in spans if s[0] == "ocl.route_pass"]
    assert {s[3]["calib"] for s in passes} == {0, 1}
    assert len(passes) == len(host)
    for (_, _, _, args, _), (i, rows, bucket, tok) in zip(passes, host):
        assert (args["level"], args["rows"], args["bucket"]) == (
            i, rows, bucket)
        if tok is None:
            assert "tokens" not in args
        else:
            assert args["tokens"] == tok
            assert args["token_slots"] == bucket * SPEC.max_len


@pytest.mark.parametrize("max_delay,per_lane", [(0, False), (1, True)],
                         ids=["per_tick", "per_lane"])
def test_profiler_leaves_results_bit_identical(tmp_path, engine, max_delay,
                                               per_lane):
    """Outputs, every state tree and the ring buffers are the same bits
    with the profiler recording the spans as without it, on the per-tick
    and the per-lane commit paths (one engine, reset in between)."""
    stream, eng = engine(max_delay, per_lane)
    want = _ticks(eng, stream, 1)[1:] + _ticks(eng, stream, 3)
    state = state_leaves(eng.levels)
    cache = [np.asarray(c) for c in eng._cache_x + eng._cache_y]
    stream, eng = engine(max_delay, per_lane)
    got = _traced(eng, stream, tmp_path)[0]
    assert len(want) == len(got) == 3
    for a, b in zip(want, got):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    for a, b in zip(state, state_leaves(eng.levels)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(cache, eng._cache_x + eng._cache_y):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_delayed_commit_names_routed_and_committing_tick(tmp_path, engine):
    """Per-lane commits at ``max_delay=1``: each lane's ``ocl.commit``
    carries the tick that routed it and the tick that commits it."""
    stream, eng = engine(1, True)
    _, spans = _traced(eng, stream, tmp_path)
    commits = [s[3] for s in spans if s[0] == "ocl.commit"]
    assert commits
    # a lane routed at tick u commits at the end of tick u + 1, except
    # the last tick's, which flush() commits at that tick
    last = eng.t
    assert all(c["at"] == c["tick"] + 1 for c in commits
               if c["tick"] < last)
    assert all(c["at"] == last for c in commits if c["tick"] == last)
    assert {c["tick"] for c in commits} == {last - 2, last - 1, last}
    assert all(c["rows"] == 1 for c in commits)
    for c in commits:
        updates = [s for s in spans if s[0] == "ocl.update"
                   and s[3]["tick"] == c["tick"]]
        assert {u[3]["step"] for u in updates} == {
            "cache_scatter", "lr.student_step", "lr.deferral_step",
            "tinytf.student_step", "tinytf.deferral_step"}
