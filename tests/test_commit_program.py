"""The per-tick commit as one donated update program (core/batched.py
``_commit``, sharding.jit_tick_update): it donates only state the engine
itself produced, dispatches once per annotated tick, and evolves the
state exactly as the per-level compiled steps it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import (assert_state_equal, batched_engine, make_setup,
                     run_ticks, state_leaves)
from repro.core.batched import BatchedCascadeEngine
from repro.core.rng import sample_cache_indices

S, TICKS = 8, 10


def _setup():
    return make_setup(3e-7, S * TICKS, dataset="hatespeech")


def _install_outside_state(eng):
    """Replace each level's student and deferral weights with arrays the
    engine did not make, and take them as the reset state — what a
    caller installing pretrained weights does.  Returns every installed
    leaf."""
    for lvl in eng.levels:
        lvl.params = jax.tree.map(jnp.copy, lvl.params)
        lvl.dparams = jax.tree.map(jnp.copy, lvl.dparams)
        lvl._init_state = (lvl.params, lvl.opt_state, lvl.dparams,
                           lvl.dopt_state)
    return [x for lvl in eng.levels for x in jax.tree.leaves(
        lvl._init_state)]


def _serve(eng, stream):
    outs = run_ticks(eng, stream, 0, TICKS)
    return np.concatenate([np.asarray(o["predictions"]) for o in outs])


def test_outside_state_survives_and_reset_reproduces():
    stream, cfg = _setup()
    eng = batched_engine(cfg, stream, n_streams=S)
    installed = _install_outside_state(eng)
    preds = _serve(eng, stream)
    assert eng.commit_stats["programs"] > 1
    assert eng.commit_stats["private_copies"] == 1
    assert not any(x.is_deleted() for x in installed)
    state = state_leaves(eng.levels)

    eng.reset()
    assert eng.commit_stats["programs"] == 0
    preds2 = _serve(eng, stream)
    assert not any(x.is_deleted() for x in installed)
    np.testing.assert_array_equal(preds, preds2)
    for a, b in zip(state, state_leaves(eng.levels)):
        np.testing.assert_array_equal(a, b)
    assert eng.commit_stats["private_copies"] == 1


def _legacy_commit(self, rec, t=None):
    """The per-tick commit as separate compiled programs: the ring
    scatter, eager mini-batch gathers, then each level's own
    ``_student_step``/``_deferral_step`` (or their ``_k`` forms)."""
    nlev = len(self.levels)
    sel_c = rec.sel_c
    k = sel_c.size
    y_sel = self._resolve_labels(rec, 0, k)
    S_t = rec.called.shape[0]
    y_full = np.zeros(S_t, np.int32)
    y_full[sel_c] = y_sel
    ptr_pre = np.asarray(self._cache_ptr, np.int32)
    idx_t = []
    for i, lvl in enumerate(self.levels):
        size = lvl.spec.cache_size
        self._cache_n[i] = min(self._cache_n[i] + k, size)
        self._cache_ptr[i] = (self._cache_ptr[i] + k) % size
        idx_t.append(jnp.asarray(sample_cache_indices(
            rec.cache_rngs[i], self._cache_n[i],
            self._bs_list[i]).astype(np.int32)))
    new_cx, new_cy = self._scatter(
        tuple(self._cache_x), tuple(self._cache_y),
        tuple(jnp.asarray(rec.feats[i]) for i in range(nlev)),
        jnp.asarray(y_full), jnp.asarray(rec.called), jnp.asarray(ptr_pre))
    self._cache_x, self._cache_y = list(new_cx), list(new_cy)
    reach = np.ones((nlev, S_t), np.float32)
    for i in range(1, nlev):
        reach[i] = reach[i - 1] * rec.dprob[i - 1]
    k_arr = (jnp.asarray(float(k), jnp.float32)
             if self.updates_per_tick == "scaled" and k > 1 else None)
    B_c = self._bucket(k)
    for i, lvl in enumerate(self.levels):
        lvl.apply_student_update(
            self._cache_x[i][idx_t[i]], self._cache_y[i][idx_t[i]],
            jnp.ones((self._bs_list[i],), jnp.float32), k_arr)
        probs_b = np.zeros((B_c, self.cfg.n_classes), np.float32)
        probs_b[:k] = rec.probs[i, sel_c]
        y_b = np.zeros(B_c, np.int32)
        y_b[:k] = y_sel
        reach_b = np.zeros(B_c, np.float32)
        reach_b[:k] = reach[i, sel_c]
        w_b = np.zeros(B_c, np.float32)
        w_b[:k] = 1.0
        lvl.apply_deferral_update(jnp.asarray(probs_b), jnp.asarray(y_b),
                                  jnp.asarray(reach_b), jnp.asarray(w_b),
                                  k_arr)
    rec.committed = k
    self._record_commit(rec, sel_c, self.t if t is None else t)
    self._state_version += 1


def _count_step_dispatches(eng, counts):
    """Count each level's standalone update-step dispatches (calls on
    concrete arrays; the update program's trace calls them on tracers)."""
    names = ("_student_step", "_student_step_k", "_deferral_step",
             "_deferral_step_k")
    for lvl in eng.levels:
        for name in names:
            f = getattr(lvl, name)

            def counted(*args, _f=f, _name=name):
                if not isinstance(args[2], jax.core.Tracer):
                    counts[_name] = counts.get(_name, 0) + 1
                return _f(*args)
            setattr(lvl, name, counted)


@pytest.mark.parametrize("updates", ["single", "scaled"])
def test_one_program_per_annotated_tick(monkeypatch, updates):
    stream, cfg = _setup()
    eng = batched_engine(cfg, stream, n_streams=S, updates_per_tick=updates)
    counts, calls = {}, []
    _count_step_dispatches(eng, counts)
    update = eng._update

    def counted_update(*args):
        calls.append(1)
        return update(*args)
    eng._update = counted_update
    outs = run_ticks(eng, stream, 0, TICKS)
    annotated = sum(bool(np.any(o["expert_called"])) for o in outs)
    assert annotated == TICKS       # the learning regime: every tick
    assert len(calls) == annotated
    assert eng.commit_stats["programs"] == annotated
    assert eng.commit_stats["private_copies"] == 1
    assert counts == {}

    # the same stream through the per-level compiled steps it replaced
    monkeypatch.setattr(BatchedCascadeEngine, "_commit", _legacy_commit)
    ref = batched_engine(cfg, stream, n_streams=S, updates_per_tick=updates)
    ref_counts = {}
    _count_step_dispatches(ref, ref_counts)
    ref_outs = run_ticks(ref, stream, 0, TICKS)
    suffix = "_k" if updates == "scaled" else ""
    assert ref_counts[f"_student_step{suffix}"] == \
        annotated * len(ref.levels)
    for a, b in zip(outs, ref_outs):
        np.testing.assert_array_equal(a["predictions"], b["predictions"])
    assert_state_equal(ref.levels, eng.levels)
    for a, b in zip(ref._cache_x + ref._cache_y,
                    eng._cache_x + eng._cache_y):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
