"""cascade-san suite: the runtime sanitizers.

Covers the trace differ on hand-built divergent traces (exact
first-divergence coordinates), the end-to-end acceptance fixtures —
corrupt one engine's level params mid-run and the differ must name the
exact (tick, level, attr); touch ``ExpertTicket._shards`` without the
lock and the lock sanitizer must raise at the access — plus lock-order
cycle detection, retrace counting, the env/contextmanager enable
surface, trace persistence, and the ``reset()`` reuse pin (a reset
engine must be indistinguishable from a fresh one, traces included).
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import (
    assert_run_parity, batched_engine, first_divergence, make_setup,
    run_pair, sequential_engine)
from repro.analysis import sanitize as san
from repro.core.experts import ExpertTicket


@pytest.fixture(autouse=True)
def _sanitizer_state_restored():
    """Every test starts from an all-off switchboard and the ambient
    state (e.g. the CI sanitizer job's CASCADE_SANITIZE env enable from
    conftest.py) is restored afterwards — the on/off assertions below
    must hold regardless of how the suite was launched."""
    prior = san.active_modes()
    san.disable()
    san.reset_retrace()
    yield
    san.disable()
    san.reset_retrace()
    if prior:
        san.enable(prior)


# ---------------------------------------------------------------------------
# trace differ on hand-built records
# ---------------------------------------------------------------------------
def rec(t, *, level=(0, 0), called=(0, 0), pred=(1, 1), rng=(11, 22),
        cache_n=(4, 4), cache_ptr=(0, 0), state=None):
    """One synthetic 2-lane, 2-level tick record."""
    return {
        "t": t,
        "level": list(level), "called": list(called), "pred": list(pred),
        "rng": list(rng),
        "cache_n": list(cache_n), "cache_ptr": list(cache_ptr),
        "state": dict(state) if state else
        {f"{li}.{a}": 7 for li in range(2)
         for a in ("params", "opt_state", "dparams", "dopt_state")},
    }


class TestDiffTraces:
    def test_identical_traces_clean(self):
        a = [rec(t) for t in range(5)]
        b = [rec(t) for t in range(5)]
        assert san.diff_traces(a, b) is None

    def test_rng_divergence_names_tick_and_lane(self):
        a = [rec(0), rec(1), rec(2)]
        b = [rec(0), rec(1), rec(2, rng=(11, 99))]
        d = san.diff_traces(a, b)
        assert (d.tick, d.lane, d.field) == (2, 1, "rng")
        assert (d.a, d.b) == (22, 99)
        assert "tick 2, lane 1" in d.describe()

    def test_routing_divergence_names_lane(self):
        a = [rec(0), rec(1, level=(0, 2), called=(0, 1))]
        b = [rec(0), rec(1, level=(0, 1), called=(0, 1))]
        d = san.diff_traces(a, b)
        assert (d.tick, d.lane, d.field) == (1, 1, "level")

    def test_state_divergence_names_level_and_attr(self):
        bad = {f"{li}.{a}": 7 for li in range(2)
               for a in ("params", "opt_state", "dparams", "dopt_state")}
        bad["1.opt_state"] = 8
        a = [rec(0), rec(1)]
        b = [rec(0), rec(1, state=bad)]
        d = san.diff_traces(a, b)
        assert (d.tick, d.level, d.attr) == (1, 1, "opt_state")
        assert d.field == "state" and d.lane is None
        assert "attr 'opt_state'" in d.describe()

    def test_params_reported_before_downstream_echoes(self):
        # a corrupted params tree perturbs dparams/opt_state digests in
        # the SAME tick record; the differ must name the cause, not an
        # alphabetically-earlier echo (dparams < params)
        bad = {f"{li}.{a}": 7 for li in range(2)
               for a in ("params", "opt_state", "dparams", "dopt_state")}
        for a in ("params", "opt_state", "dparams", "dopt_state"):
            bad[f"1.{a}"] = 9
        d = san.diff_traces([rec(3)], [rec(3, state=bad)])
        assert (d.tick, d.level, d.attr) == (3, 1, "params")

    def test_rng_checked_before_state(self):
        # a diverged key stream also moves state; the differ must name
        # the upstream cause (the lane's RNG), not the state echo
        bad = {f"{li}.{a}": 9 for li in range(2)
               for a in ("params", "opt_state", "dparams", "dopt_state")}
        d = san.diff_traces([rec(0)], [rec(0, rng=(11, 99), state=bad)])
        assert d.field == "rng" and d.lane == 1

    def test_cache_mirror_divergence_names_level(self):
        a = [rec(0, cache_ptr=(0, 3))]
        b = [rec(0, cache_ptr=(0, 4))]
        d = san.diff_traces(a, b)
        assert (d.field, d.level) == ("cache_ptr", 1)

    def test_length_mismatch_diverges_at_first_missing(self):
        a = [rec(0), rec(1), rec(2)]
        b = [rec(0), rec(1)]
        d = san.diff_traces(a, b)
        assert (d.field, d.tick, d.index) == ("length", 2, 2)
        assert (d.a, d.b) == (3, 2)

    def test_tick_number_mismatch(self):
        d = san.diff_traces([rec(0), rec(1)], [rec(0), rec(5)])
        assert d.field == "t" and (d.a, d.b) == (1, 5)

    def test_trace_objects_accepted(self):
        ta, tb = san.Trace(), san.Trace()
        for t in range(3):
            ta.append(rec(t))
            tb.append(rec(t))
        assert san.diff_traces(ta, tb) is None
        assert len(ta) == 3


class TestTracePersistence:
    def test_save_load_roundtrip(self, tmp_path):
        tr = san.Trace()
        for t in range(4):
            tr.append(rec(t, rng=(t, t + 1)))
        path = str(tmp_path / "trace.jsonl")
        tr.save(path)
        back = san.Trace.load(path)
        assert back.ticks == tr.ticks
        assert san.diff_traces(tr, back) is None


# ---------------------------------------------------------------------------
# enable surface
# ---------------------------------------------------------------------------
class TestEnableSurface:
    def test_enable_disable_roundtrip(self):
        san.enable({"determinism"})
        assert san.determinism_on()
        san.disable({"determinism"})
        assert not san.determinism_on()

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="unknown sanitize mode"):
            san.enable({"quantum"})

    def test_enable_from_env(self, monkeypatch):
        monkeypatch.setenv(san.ENV_VAR, "determinism, retrace")
        assert san.enable_from_env() == {"determinism", "retrace"}
        assert san.determinism_on() and san.retrace_on()

    def test_enable_from_env_unset_is_noop(self, monkeypatch):
        monkeypatch.delenv(san.ENV_VAR, raising=False)
        before = san.active_modes()
        assert san.enable_from_env() == set()
        assert san.active_modes() == before

    def test_determinism_trace_restores_prior_state(self):
        assert not san.determinism_on()
        with san.determinism_trace():
            assert san.determinism_on()
        assert not san.determinism_on()
        san.enable({"determinism"})
        with san.determinism_trace():
            pass
        assert san.determinism_on()   # pre-existing enable survives


# ---------------------------------------------------------------------------
# determinism sanitizer on the real engines
# ---------------------------------------------------------------------------
class TestDeterminismSanitizer:
    def test_sequential_and_batched_traces_align(self):
        stream, cfg = make_setup(mu=0.05, n=40)
        seq = sequential_engine(cfg, stream)
        bat = batched_engine(cfg, stream, n_streams=1)
        m_ref, m_new = run_pair(seq, bat, stream)
        ta, tb = san.trace_of(seq), san.trace_of(bat)
        assert ta is not None and len(ta) == 40
        assert tb is not None and len(tb) == 40
        assert san.diff_traces(ta, tb) is None
        assert first_divergence(seq, bat) is None
        assert_run_parity(seq, m_ref, bat, m_new)

    def test_corrupted_lane_params_named_exactly(self):
        # THE acceptance fixture: corrupt one engine's level-1 params
        # mid-run and the differ must name the exact (tick, level, attr)
        # — not "params mismatch somewhere" at stream end
        stream, cfg = make_setup(mu=0.05, n=40)
        a = batched_engine(cfg, stream, n_streams=2)
        b = batched_engine(cfg, stream, n_streams=2)
        S = 2
        with san.determinism_trace():
            for start in range(0, len(stream), S):
                idxs = list(range(start, min(start + S, len(stream))))
                docs = [stream.docs[i] for i in idxs]
                if b.t == 7:
                    leaves, tdef = jax.tree.flatten(b.levels[1].params)
                    leaves[0] = leaves[0].at[0].add(1.0)
                    b.levels[1].params = jax.tree.unflatten(tdef, leaves)
                a.process_tick(idxs, docs)
                b.process_tick(idxs, docs)
            a.flush(), b.flush()
        d = san.diff_traces(san.trace_of(a), san.trace_of(b))
        assert d is not None
        assert d.field == "state"
        # tick labels are 1-based (dispatch pre-increments self.t), so
        # the first tick served AFTER the b.t==7 injection is tick 8 —
        # and the attr must be the corrupted 'params', not a same-tick
        # optimizer/deferral echo
        assert (d.tick, d.level, d.attr) == (8, 1, "params"), d.describe()
        assert "level 1, attr 'params'" in d.describe()

    def test_no_trace_recorded_when_off(self):
        stream, cfg = make_setup(mu=0.05, n=8)
        eng = batched_engine(cfg, stream, n_streams=2)
        assert not san.determinism_on()
        eng.run(stream)
        assert san.trace_of(eng) is None

    def test_reset_drops_trace(self):
        stream, cfg = make_setup(mu=0.05, n=8)
        eng = batched_engine(cfg, stream, n_streams=2)
        with san.determinism_trace():
            eng.run(stream)
        assert san.trace_of(eng) is not None
        eng.reset()
        assert san.trace_of(eng) is None


# ---------------------------------------------------------------------------
# reset() reuse pin: a reset engine is indistinguishable from a fresh one
# ---------------------------------------------------------------------------
class TestResetReuse:
    def test_reset_engine_replays_stream_identically(self):
        stream, cfg = make_setup(mu=0.05, n=32)
        fresh = batched_engine(cfg, stream, n_streams=2)
        reused = batched_engine(cfg, stream, n_streams=2)
        with san.determinism_trace():
            reused.run(stream)        # warm-up serve on the same stream
            reused.reset()
            m_fresh, m_reused = fresh.run(stream), reused.run(stream)
        assert_run_parity(fresh, m_fresh, reused, m_reused,
                          history_keys=("level", "expert_called"),
                          costs=True)
        d = san.diff_traces(san.trace_of(fresh), san.trace_of(reused))
        assert d is None, d.describe()

    def test_reset_zeroes_the_stats_surface(self):
        stream, cfg = make_setup(mu=0.05, n=16)
        eng = batched_engine(cfg, stream, n_streams=2)
        eng.run(stream)
        eng.reset()
        assert eng.t == 0
        assert not np.any(eng.expert_calls)
        assert not np.any(eng.total_cost)
        assert not np.any(eng.level_counts)
        assert not np.any(eng.items_seen)
        assert not np.any(eng.J_cum)
        assert eng.commit_stats == {"lanes": 0, "age_sum": 0,
                                    "age_max": 0, "wall_sum": 0.0,
                                    "programs": 0, "private_copies": 0}
        assert all(v == 0 for v in eng.pipeline_stats.values())
        assert eng._cache_n == [0] * len(eng.levels)
        assert eng._cache_ptr == [0] * len(eng.levels)
        assert all(len(v) == 0 for v in (eng.history or {}).values())


# ---------------------------------------------------------------------------
# lock sanitizer
# ---------------------------------------------------------------------------
class TestLockSanitizer:
    def test_unguarded_shards_access_raises(self):
        # runtime twin of the CAS004 static acceptance fixture: a bare
        # read of ExpertTicket._shards outside the lock must raise AT
        # THE ACCESS, and a guarded read must pass untouched
        san.enable({"locks"})
        ticket = ExpertTicket(labels=np.array([1, 0, 1]))
        with pytest.raises(san.LockSanitizerError,
                           match=r"_shards read .* guarded-by"):
            ticket._shards
        with ticket._lock:
            assert len(ticket._shards) == 1
        assert ticket.done()          # the guarded API is unaffected

    def test_unguarded_write_raises(self):
        san.enable({"locks"})
        ticket = ExpertTicket(labels=np.array([1]))
        with pytest.raises(san.LockSanitizerError, match="write"):
            ticket._shards = []

    def test_disable_restores_bare_access(self):
        san.enable({"locks"})
        ticket = ExpertTicket(labels=np.array([1, 0]))
        san.disable({"locks"})
        assert len(ticket._shards) == 1   # instrumentation fully undone

    def test_instrumentation_is_idempotent(self):
        first = san.instrument_locks()
        again = san.instrument_locks()
        assert first == again and "ExpertTicket._shards" in first
        san.uninstrument_locks()

    def test_expert_pool_runs_clean_under_lock_sanitizer(self):
        # the real engine's concurrent ticket traffic must not trip the
        # sanitizer: every access in experts.py honours its annotation
        san.enable({"locks"})
        stream, cfg = make_setup(mu=0.05, n=24)
        eng = batched_engine(cfg, stream, n_streams=2,
                             expert_kw={"workers": 4})
        eng.run(stream)
        assert san.lock_order_violations() == []

    def test_lock_order_cycle_detected(self):
        la = san.tracked_rlock("A")
        lb = san.tracked_rlock("B")
        try:
            with la:
                with lb:
                    pass
            with pytest.raises(san.LockOrderError, match="cycle"):
                with lb:
                    with la:
                        pass
            assert len(san.lock_order_violations()) == 1
        finally:
            san._held.stack = []      # the raising acquire left a frame
            san.uninstrument_locks()  # clears the order graph


# ---------------------------------------------------------------------------
# retrace sanitizer
# ---------------------------------------------------------------------------
class TestRetraceSanitizer:
    def test_probe_is_identity_when_off(self):
        def f(x):
            return x
        assert san.trace_probe("f", f) is f

    def test_counts_compiles_not_calls(self):
        san.enable({"retrace"})
        san.reset_retrace()
        step = jax.jit(san.trace_probe("step", lambda x: x * 2))
        step(jnp.ones((4,)))
        step(jnp.ones((4,)))          # cache hit: no retrace
        assert san.retrace_report() == {"step": 1}
        step(jnp.ones((8,)))          # new shape: one retrace
        assert san.retrace_report() == {"step": 2}
        assert san.retrace_check(limit=2) == {}
        assert san.retrace_check(limit=1) == {"step": 2}

    def test_engine_compile_counts_are_bounded(self):
        san.enable({"retrace"})
        san.reset_retrace()
        stream, cfg = make_setup(mu=0.05, n=24)
        eng = batched_engine(cfg, stream, n_streams=2)
        eng.run(stream)
        report = san.retrace_report()
        assert report, "no probed step function compiled"
        # bucketing bounds compiled shapes at O(log S); a leak would
        # show up as one compile per tick (12 ticks here)
        assert san.retrace_check(limit=8) == {}, report


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
