#!/usr/bin/env python3
"""Record what the benchmark's reference side computes for
``bert-learn-s64`` into ``data/golden_kinds.json``; ``test_kinds.py``
holds every later tree to it, bit for bit.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tests/record_golden.py

At the cell's test size (``conftest.tiny_cell``): each leaf of the seed's
weights and of the balanced expert, each level's featurized rows, each
level's reference probabilities and deferral probabilities at full
precision and one below it, the expert's logits, the learning replay's
first-gradient and parameter-change leaf norms over three ticks of
fixed labels, and ``flops.window_flops`` over a fixed tick history.  At
the cell's own size: the weight tree's structure and leaf shapes, and the
window FLOPs over a like history.  Record on the tree whose numbers are
the standard; a change that moves none of them reproduces the file.
"""
import hashlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

CELL = "bert-learn-s64"
SEED = 2 ** 31 + 5151
OUT = HERE / "data" / "golden_kinds.json"


def _digest(a) -> str:
    import numpy as np
    a = np.ascontiguousarray(np.asarray(a))
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(a.tobytes()).hexdigest()


def _leaves(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): _digest(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _floats(a) -> list:
    import numpy as np
    return [float(x) for x in np.asarray(a, np.float64).ravel()]


def _history(S: int, nlev: int, ticks: int = 5):
    """A fixed tick history: each lane's exit level and expert call."""
    import numpy as np
    rng = np.random.default_rng(77)
    levels = [rng.integers(0, nlev + 1, S) for _ in range(ticks)]
    called = [(lv == nlev) | (rng.random(S) < 0.2) for lv in levels]
    return levels, called


def golden() -> dict:
    """Everything the golden file holds, computed on this tree."""
    import jax
    import numpy as np

    import check
    import conftest
    import flops
    import model
    import run
    import traffic

    c = conftest.tiny_cell(CELL)
    cfg, mix = c["cfg"], c["mix"]
    casc = cfg["cascade"]
    levels = casc["levels"]
    nf = casc["n_features"]
    S = mix["lanes"]
    docs, _ = traffic.corpus(mix, SEED)
    w = model.make_weights(casc, SEED)
    out = {"weights": _leaves(w)}
    balanced = model.balance_expert(w["expert"], casc, docs[:256])
    out["balanced_expert"] = _leaves(balanced)

    sample = docs[:16]
    ref, low = check.Precision(False), check.Precision(True)
    out["rows"], out["probs"] = [], []
    for lv, lw in zip(levels, w["levels"]):
        x = model.featurize(lv, sample, nf)
        out["rows"].append(_digest(x))
        per = {}
        for name, prec in (("reference", ref), ("control", low)):
            p, d = check._forward(lv, prec, lw["student"], lw["deferral"], x)
            per[name] = {"probs": _floats(p), "dprob": _floats(d)}
        out["probs"].append(per)
    out["expert_logits"] = {
        name: _floats(check._expert(casc, prec, balanced, sample))
        for name, prec in (("reference", ref), ("control", low))}

    rng = np.random.default_rng(78)
    docs_by_tick = {t: docs[16 + S * t:16 + S * (t + 1)] for t in (1, 2, 3)}
    outs_by_tick = {t: {"expert_called": np.ones(S, bool),
                        "expert_labels": rng.integers(0, 2, S)}
                    for t in docs_by_tick}
    w["expert"] = balanced
    rep = check.replay_learning(cfg, mix, SEED, w, docs_by_tick,
                                outs_by_tick, ref)
    out["replay"] = {"grad_norms": rep["grad_norms"],
                     "delta_norms": rep["delta_norms"],
                     "expert_logits": [_floats(t["expert_logits"])
                                       for t in rep["ticks"]]}

    hist = _history(S, len(levels))
    out["window_flops"] = flops.window_flops(casc, *hist)

    full = run.load_cell(CELL)["cfg"]["cascade"]
    shapes = jax.eval_shape(lambda: model.make_weights(full, SEED))
    out["full"] = {
        "structure": str(jax.tree.structure(shapes)),
        "leaves": {jax.tree_util.keystr(p): f"{x.dtype}{list(x.shape)}"
                   for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0]},
        "window_flops": flops.window_flops(full, *_history(64, len(levels))),
        "level_flops": [flops.level_flops(lv, full) for lv in full["levels"]]}
    return out


def main() -> None:
    OUT.write_text(json.dumps(golden(), indent=1, sort_keys=True) + "\n")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
