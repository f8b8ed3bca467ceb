"""Chaos suite: the elastic expert fleet's failure semantics.

Everything here rides ``FlakyExpert`` (core/experts.py) — scripted or
seeded per-(submit, shard) faults over a real expert whose labels are
deterministic functions of the items.  That makes the contracts sharp:

* every deferred item is committed exactly once — within its D-tick
  deadline when any retry succeeds, or as an explicitly counted
  ``dropped_annotations`` degradation after ``max_requeues`` — never
  silently, never twice, never deadlocking;
* fault TIMING never changes committed state: a run under injected
  timeouts/deaths whose annotations all eventually land is bitwise the
  fault-free run (requeues re-derive identical labels);
* the opt-in readiness-commit mode stays inside the documented
  commit-age bound while preserving commit order.
"""
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # degrade gracefully: only property tests skip
    from _hypothesis_stubs import given, settings, st

import harness as H
from repro.core import FlakyExpert
from repro.core.experts import (ExpertShardTimeout, ExpertTicket,
                                ExpertWorkerDied, _fault_draw)

N, S = 64, 4
MU = 3e-6


def _setup(n=N, dataset="hatespeech"):
    return H.make_setup(mu=MU, n=n, dataset=dataset)


def _run(engine, stream, n_ticks):
    outs = H.run_ticks(engine, stream, 0, n_ticks)
    return H.finish_run(engine, outs)


# ---------------------------------------------------------------------------
# ticket-level failure primitives
# ---------------------------------------------------------------------------
def test_ticket_replace_splices_requeued_shard():
    t = ExpertTicket(shards=[(0, 2, np.array([1, 2], np.int32)),
                             (2, 4, np.array([3, 4], np.int32))])
    t.replace(2, 4, ExpertTicket(labels=np.array([7, 8], np.int32)))
    np.testing.assert_array_equal(t.result(), [1, 2, 7, 8])


def test_ticket_force_resolve_drops_to_sentinel():
    t = ExpertTicket(shards=[(0, 3, np.array([1, 2, 3], np.int32))])
    t.force_resolve(0, 3, np.full(3, -1, np.int32))
    np.testing.assert_array_equal(t.result(), [-1, -1, -1])


def test_flaky_timeout_shard_raises_expert_shard_timeout():
    stream, _ = _setup(8)
    ex = FlakyExpert(H.make_expert(stream, workers=2),
                     schedule=lambda seq, j: "timeout" if j == 0 else None)
    ticket = ex.submit_many(list(range(8)), [stream.docs[i]
                                             for i in range(8)])
    with pytest.raises(ExpertShardTimeout) as ei:
        ticket.result_slice(0, 8, timeout=0.01)
    assert (ei.value.lo, ei.value.hi) == (0, 4)
    assert ex.injected["timeout"] == 1


def test_flaky_dead_worker_raises_expert_worker_died():
    stream, _ = _setup(8)
    ex = FlakyExpert(H.make_expert(stream, workers=2),
                     schedule=lambda seq, j: "die" if j == 1 else None)
    ticket = ex.submit_many(list(range(8)), [stream.docs[i]
                                             for i in range(8)])
    # the dead shard reports done (its future is settled with an error)
    assert ticket.item_done(4)
    with pytest.raises(ExpertWorkerDied):
        ticket.result_slice(4, 8)


def test_fault_draws_are_replayable():
    draws = [_fault_draw(7, seq, j, "t") for seq in range(20)
             for j in range(4)]
    again = [_fault_draw(7, seq, j, "t") for seq in range(20)
             for j in range(4)]
    assert draws == again
    assert all(0.0 <= d < 1.0 for d in draws)
    assert len(set(draws)) > 50          # actually varies per cell


# ---------------------------------------------------------------------------
# kill-a-worker mid-ticket: requeue lands the SAME labels on time
# ---------------------------------------------------------------------------
def test_kill_worker_mid_ticket_requeue_restores_labels():
    """A worker dying mid-ticket requeues its shard; the retry derives
    identical labels, so the run is bitwise the fault-free one and
    nothing is dropped."""
    stream, cfg = _setup()
    n_ticks = N // S
    clean = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                             expert_kw={"workers": 2})
    clean_outs = _run(clean, stream, n_ticks)

    # die on the first attempt of submit 3's shard 0; retries (fresh
    # submit seqs) succeed
    deaths = []

    def schedule(seq, j):
        if seq == 3 and j == 0:
            deaths.append(seq)
            return "die"
        return None

    chaos = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                           expert_kw={"workers": 2},
                           flaky_kw={"schedule": schedule})
    chaos_outs = _run(chaos, stream, n_ticks)

    assert chaos.expert.injected["die"] == len(deaths) == 1
    assert chaos.fault_stats["worker_deaths"] == 1
    assert chaos.fault_stats["requeues"] == 1
    assert chaos.fault_stats["dropped_annotations"] == 0
    a, b = H.collate_outputs(clean_outs), H.collate_outputs(chaos_outs)
    for key in ("predictions", "levels", "expert_called"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    H.assert_state_equal(clean.levels, chaos.levels)
    # requeues are not re-counted: annotation was costed at route time
    assert (H.expert_calls_total(clean) == H.expert_calls_total(chaos))


# ---------------------------------------------------------------------------
# timeout -> requeue exactly-once commit (property + concrete twin)
# ---------------------------------------------------------------------------
def _chaos_run_commits_exactly_once(fail_cells, max_requeues):
    """Shared body: run a chaos schedule, assert the exactly-once commit
    accounting, and return the engine (for further assertions).

    ``fail_cells`` maps a submit sequence to how many consecutive
    attempts of its shard 0 fail (requeues get fresh seqs, so attempt r
    of original submit q is approximated by failing ANY submit whose
    seq is in the scripted set — the count discipline below only needs
    "fails then eventually succeeds-or-drops").
    """
    stream, cfg = _setup()
    n_ticks = N // S
    attempts = {}

    def schedule(seq, j):
        if j != 0:
            return None
        budget = fail_cells.get(seq % 7, 0)
        seen = attempts.get(seq, 0)
        attempts[seq] = seen + 1
        return "timeout" if seen < budget else None

    eng = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                         expert_kw={"workers": 2},
                         flaky_kw={"schedule": schedule},
                         expert_timeout=0.01, max_requeues=max_requeues)
    outs = _run(eng, stream, n_ticks)
    col = H.collate_outputs(outs)
    # exactly-once: every item commits exactly once -> one output row
    # per stream item, and the deferred accounting balances exactly
    assert col["predictions"].shape == (N,)
    assert np.all(col["predictions"] >= 0)
    assert len(eng._pending) == 0 and len(eng._ring) == 0
    fs = eng.fault_stats
    # every timeout event either requeued or terminated in a drop —
    # no fault event vanishes without an accounted outcome
    assert fs["requeues"] <= fs["timeouts"]
    if fs["dropped_annotations"] == 0:
        assert fs["requeues"] == fs["timeouts"]
    return eng, col


def test_timeout_requeue_exactly_once_concrete():
    """Concrete twin of the property: one scripted timeout, generous
    max_requeues — no drop, bitwise the clean run."""
    stream, cfg = _setup()
    n_ticks = N // S
    clean = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                             expert_kw={"workers": 2})
    clean_outs = _run(clean, stream, n_ticks)

    first = {}

    def schedule(seq, j):
        # first attempt of every 5th submit's shard 0 times out
        if j == 0 and seq % 5 == 0 and seq not in first:
            first[seq] = True
            return "timeout"
        return None

    eng = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                         expert_kw={"workers": 2},
                         flaky_kw={"schedule": schedule},
                         expert_timeout=0.01, max_requeues=3)
    outs = _run(eng, stream, n_ticks)
    assert eng.fault_stats["requeues"] == eng.fault_stats["timeouts"] > 0
    assert eng.fault_stats["dropped_annotations"] == 0
    a, b = H.collate_outputs(clean_outs), H.collate_outputs(outs)
    for key in ("predictions", "levels", "expert_called"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    H.assert_state_equal(clean.levels, eng.levels)


@settings(max_examples=15, deadline=None)
@given(fail_seqs=st.dictionaries(st.integers(0, 6), st.integers(1, 4),
                                 max_size=4),
       max_requeues=st.integers(0, 3))
def test_timeout_requeue_exactly_once_property(fail_seqs, max_requeues):
    """Property: whatever the (timeout schedule, max_requeues) draw,
    every deferred item commits exactly once — either a real label
    within its deadline or a counted drop — and the engine terminates
    with empty queues (no deadlock, no silent drop)."""
    eng, col = _chaos_run_commits_exactly_once(fail_seqs, max_requeues)
    fs = eng.fault_stats
    # drops only happen after exhausting the requeue budget
    if max_requeues >= 5:
        assert fs["dropped_annotations"] == 0
    assert fs["requeues"] <= fs["timeouts"]
    eng.close()


# ---------------------------------------------------------------------------
# max_requeues graceful degradation: never deadlocks, drops are counted
# ---------------------------------------------------------------------------
def test_max_requeues_graceful_degradation_never_deadlocks():
    """An always-failing shard exhausts its requeue budget and degrades:
    the lane commits its provisional student answer, the loss is counted
    in dropped_annotations, and the run terminates."""
    stream, cfg = _setup()
    n_ticks = N // S

    def schedule(seq, j):
        return "timeout"          # EVERY shard of EVERY submit hangs

    eng = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                         expert_kw={"workers": 2},
                         flaky_kw={"schedule": schedule},
                         expert_timeout=0.01, max_requeues=2)
    done = threading.Event()
    box = {}

    def drive():
        box["outs"] = _run(eng, stream, n_ticks)
        done.set()

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    assert done.wait(timeout=300), "chaos run deadlocked"
    col = H.collate_outputs(box["outs"])
    assert col["predictions"].shape == (N,)
    assert np.all(col["predictions"] >= 0)   # provisional answers stand
    fs = eng.fault_stats
    assert fs["dropped_annotations"] > 0
    # every drop exhausted its requeue budget first (max_requeues=2
    # retries per shard before the terminal force-resolve)
    assert fs["requeues"] > 0
    assert fs["requeues"] < fs["timeouts"]
    assert len(eng._pending) == 0 and len(eng._ring) == 0
    # drops never update the student: expert_calls still counts routed
    # items, but the cache never saw the dropped labels — just assert
    # the engine is still servable afterwards
    eng.reset()
    assert eng.fault_stats["dropped_annotations"] == 0


def test_zero_max_requeues_drops_immediately():
    stream, cfg = _setup(16)
    eng = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                         expert_kw={"workers": 2},
                         flaky_kw={"schedule": lambda q, j: "die"},
                         max_requeues=0)
    outs = _run(eng, stream, 16 // S)
    col = H.collate_outputs(outs)
    assert col["predictions"].shape == (16,)
    assert eng.fault_stats["requeues"] == 0
    assert eng.fault_stats["dropped_annotations"] > 0


# ---------------------------------------------------------------------------
# deterministic default schedule is bitwise invariant to injected latency
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("flaky_kw", [
    {"slow_rate": 0.5, "slow_credits": 3, "seed": 11},
    {"schedule": lambda seq, j: ("slow", 5) if seq % 3 == 0 else None},
])
def test_bitwise_invariant_to_injected_latency(flaky_kw):
    """Slow shards shift WHEN labels become observable, never what they
    are; the deterministic lanes_due commit schedule depends only on
    tick age — so the run is bitwise the fault-free one."""
    stream, cfg = _setup()
    n_ticks = N // S
    clean = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                             per_lane=True, expert_kw={"workers": 2})
    clean_outs = _run(clean, stream, n_ticks)
    chaos = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                           per_lane=True, expert_kw={"workers": 2},
                           flaky_kw=flaky_kw)
    chaos_outs = _run(chaos, stream, n_ticks)
    assert chaos.expert.injected["slow"] > 0
    a, b = H.collate_outputs(clean_outs), H.collate_outputs(chaos_outs)
    for key in ("predictions", "levels", "expert_called"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    H.assert_state_equal(clean.levels, chaos.levels)
    np.testing.assert_array_equal(np.asarray(clean.expert_calls),
                                  np.asarray(chaos.expert_calls))


def test_bitwise_invariant_to_fault_timing_with_recovery():
    """Timeout-then-recover chaos (all annotations eventually land)
    commits bitwise-identical state: requeues re-derive the same
    labels, so only PERMANENT drops may ever diverge a run."""
    stream, cfg = _setup()
    n_ticks = N // S
    clean = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                             expert_kw={"workers": 2})
    clean_outs = _run(clean, stream, n_ticks)
    seen = set()

    def schedule(seq, j):
        if j == 1 and seq % 4 == 1 and seq not in seen:
            seen.add(seq)
            return "timeout"
        return None

    chaos = H.flaky_engine(cfg, stream, n_streams=S, max_delay=2,
                           expert_kw={"workers": 2},
                           flaky_kw={"schedule": schedule},
                           expert_timeout=0.01, max_requeues=4)
    chaos_outs = _run(chaos, stream, n_ticks)
    assert chaos.fault_stats["requeues"] > 0
    assert chaos.fault_stats["dropped_annotations"] == 0
    a, b = H.collate_outputs(clean_outs), H.collate_outputs(chaos_outs)
    np.testing.assert_array_equal(a["predictions"], b["predictions"])
    H.assert_state_equal(clean.levels, chaos.levels)


# ---------------------------------------------------------------------------
# readiness commits: opt-in early drain inside the age bound
# ---------------------------------------------------------------------------
def test_readiness_commits_within_age_bound():
    """readiness_commits=True may commit a lane as soon as its
    annotation lands (age 0: ready within the submit tick) but never
    past the deterministic deadline — every commit age is in [0, D]."""
    stream, cfg = _setup()
    D = 3
    eng = H.batched_engine(cfg, stream, n_streams=S, max_delay=D,
                           expert_kw={"workers": 2},
                           readiness_commits=True)
    _run(eng, stream, N // S)
    cs = eng.commit_stats
    assert cs["lanes"] > 0
    assert 0 <= cs["age_max"] <= D
    assert cs["age_sum"] / cs["lanes"] <= D


def test_readiness_commits_beat_deadline_with_fast_expert():
    """With a zero-latency expert, readiness mode commits strictly
    earlier on average than the deterministic deadline schedule (that is
    its point), while predictions per item may differ only through the
    documented earlier-update trajectory."""
    stream, cfg = _setup()
    D = 3
    base = H.batched_engine(cfg, stream, n_streams=S, max_delay=D,
                            expert_kw={"workers": 2})
    _run(base, stream, N // S)
    eager = H.batched_engine(cfg, stream, n_streams=S, max_delay=D,
                             expert_kw={"workers": 2},
                             readiness_commits=True)
    _run(eager, stream, N // S)
    b, e = base.commit_stats, eager.commit_stats
    # earlier commits shift updates earlier, which legitimately changes
    # later routing — so deferral COUNTS may differ; the contract is the
    # age distribution: readiness commits strictly beat the deadline
    # schedule on average and never exceed its bound
    assert b["lanes"] > 0 and e["lanes"] > 0
    assert e["age_sum"] / e["lanes"] < b["age_sum"] / b["lanes"]
    assert e["age_max"] <= b["age_max"] <= D


def test_readiness_commits_hung_shard_falls_to_deadline():
    """A hung shard cannot be committed early; readiness mode falls back
    to the D-tick deadline and the requeue path — never earlier, never
    deadlocked."""
    stream, cfg = _setup()
    seen = set()

    def schedule(seq, j):
        if seq % 6 == 2 and seq not in seen:
            seen.add(seq)
            return "timeout"
        return None

    eng = H.flaky_engine(cfg, stream, n_streams=S, max_delay=3,
                         expert_kw={"workers": 2},
                         flaky_kw={"schedule": schedule},
                         expert_timeout=0.01, max_requeues=3,
                         readiness_commits=True)
    outs = _run(eng, stream, N // S)
    col = H.collate_outputs(outs)
    assert col["predictions"].shape == (N,)
    assert eng.commit_stats["age_max"] <= 3
    assert len(eng._pending) == 0


# ---------------------------------------------------------------------------
# autoscaling: deterministic tick-boundary decisions
# ---------------------------------------------------------------------------
def test_autoscale_decisions_are_deterministic():
    stream, cfg = _setup()

    def build():
        return H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                                expert_kw={"workers": "auto"},
                                autoscale=(1, 4))

    a, b = build(), build()
    _run(a, stream, N // S)
    _run(b, stream, N // S)
    assert a.fleet_log == b.fleet_log
    assert a.expert.workers == b.expert.workers
    H.assert_state_equal(a.levels, b.levels)


def test_autoscale_matches_fixed_width_bitwise():
    """Autoscaling only resizes future shard layouts; labels are
    item-deterministic, so the run is bitwise a fixed-width run."""
    stream, cfg = _setup()
    fixed = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                             expert_kw={"workers": 2})
    fixed_outs = _run(fixed, stream, N // S)
    auto = H.batched_engine(cfg, stream, n_streams=S, max_delay=2,
                            expert_kw={"workers": "auto"},
                            autoscale=(1, 4))
    auto_outs = _run(auto, stream, N // S)
    a, b = H.collate_outputs(fixed_outs), H.collate_outputs(auto_outs)
    for key in ("predictions", "levels", "expert_called"):
        np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    H.assert_state_equal(fixed.levels, auto.levels)


# ---------------------------------------------------------------------------
# pool lifecycle: reset()/close() shut the expert pool down (leak check)
# ---------------------------------------------------------------------------
def test_model_expert_pool_closed_on_engine_reset():
    """Regression: the engine's reset()/teardown must close the expert's
    worker pool — thread count returns to baseline instead of leaking
    one pool per reset."""
    from repro.core.experts import ModelExpert
    from repro.models.students import tinytf_init, TinyTFSpec
    import jax
    spec = TinyTFSpec(vocab=64, max_len=8, d_model=16, n_heads=2,
                      n_layers=1, d_ff=32, n_classes=2)
    params = tinytf_init(jax.random.PRNGKey(0), spec)
    stream, cfg = _setup(16)
    before = threading.active_count()
    for _ in range(3):
        ex = ModelExpert(params=params, spec=spec, workers=2)
        eng = H.batched_engine(cfg, stream, n_streams=S, max_delay=2)
        eng.expert = ex
        # spin the pool up, then tear down through the engine paths
        ex.poll(ex.submit_many([0, 1],
                               [stream.docs[0], stream.docs[1]]))
        assert threading.active_count() > before
        eng.reset()
        assert ex._executor is None or ex._executor._shutdown
    # pools closed: no thread leak across 3 engine generations
    assert threading.active_count() <= before + 1


def test_engine_close_is_idempotent():
    stream, cfg = _setup(16)
    eng = H.batched_engine(cfg, stream, n_streams=S)
    eng.close()
    eng.close()
    eng.reset()


def test_model_expert_process_backend_matches_thread():
    """backend="process" spawns annotator children that produce labels
    identical to the thread pool (same params, same shard layout), and
    close() reaps them."""
    from repro.core.experts import ModelExpert
    from repro.models.students import tinytf_init, TinyTFSpec
    import jax
    stream, _ = _setup(8)
    spec = TinyTFSpec(vocab=64, max_len=8, d_model=16, n_heads=2,
                      n_layers=1, d_ff=32, n_classes=2)
    params = tinytf_init(jax.random.PRNGKey(0), spec)
    th = ModelExpert(params=params, spec=spec, workers=2,
                     backend="thread")
    pr = ModelExpert(params=params, spec=spec, workers=2,
                     backend="process")
    idxs, docs = list(range(8)), stream.docs[:8]
    try:
        a = th.poll(th.submit_many(idxs, docs))
        b = pr.poll(pr.submit_many(idxs, docs))
        np.testing.assert_array_equal(a, b)
    finally:
        pr.close()
        th.close()
    assert pr._executor is None or pr._executor._shutdown_thread


def test_model_expert_process_backend_refused_off_cpu(monkeypatch):
    """A chip belongs to one process: off the CPU backend the parent
    already holds the device, so backend="process" must refuse to start
    rather than spawn children that would hang reaching it."""
    from repro.core.experts import ModelExpert
    from repro.models.students import tinytf_init, TinyTFSpec
    import jax
    spec = TinyTFSpec(vocab=64, max_len=8, d_model=16, n_heads=2,
                      n_layers=1, d_ff=32, n_classes=2)
    params = tinytf_init(jax.random.PRNGKey(0), spec)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process"):
        ModelExpert(params=params, spec=spec, backend="process")
    ModelExpert(params=params, spec=spec, backend="thread").close()
