"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, window, check) on the CPU at test size, with the cell's own
limits, after breaking the program where a fault can sit:

- a step that returns its state unchanged;
- half of the batch left out, the mean taken over the rest;
- an answer altered where it is produced;
- a route pass that skips its last encoder layer.

A sound run of the same size must come out correct, so that the faults
and not the size fail the others.  (The exchange between chips is a
fault of four-chip cells; the cell here has one chip.)
"""
import numpy as np

from conftest import tiny_cell

SEED = 2 ** 31 + 4242


def run_tiny(name="bert-learn-s64", **kw):
    import run
    return run.run_cell(tiny_cell(name), SEED, 1.5, False,
                        check_device=False, **kw)


def test_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]


def test_unchanged_state_fails(monkeypatch):
    from repro.core.cascade import _Level
    monkeypatch.setattr(_Level, "apply_student_update",
                        lambda self, xb, yb, w, k=None: None)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["delta_gap"]["value"] > \
        out["checks"]["delta_gap"]["limit"]


def test_half_batch_fails(monkeypatch):
    from repro.core.cascade import _Level
    orig = _Level.apply_student_update

    def half(self, xb, yb, w, k=None):
        n = xb.shape[0] // 2
        return orig(self, xb[:n], yb[:n], w[:n], k)
    monkeypatch.setattr(_Level, "apply_student_update", half)
    out = run_tiny()
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k.startswith("grad_gap."))


def test_altered_answer_fails(monkeypatch):
    from repro.core.batched import BatchedCascadeEngine
    orig = BatchedCascadeEngine._route_resolve

    def altered(self, rec):
        out = orig(self, rec)
        out["predictions"] = 1 - np.asarray(out["predictions"])
        return out
    monkeypatch.setattr(BatchedCascadeEngine, "_route_resolve", altered)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["route_gap"]["value"] == 1.0


def test_dropped_layer_fails(monkeypatch):
    import repro.core.cascade as cascade
    orig = cascade.tinytf_predict

    def dropped(params, tokens, spec):
        return orig({**params, "layers": params["layers"][:-1]}, tokens,
                    spec)
    monkeypatch.setattr(cascade, "tinytf_predict", dropped)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["prob_gap"]["value"] > \
        out["checks"]["prob_gap"]["limit"]
