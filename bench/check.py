"""How ``correct`` is decided: the program against the plain reference.

Numbers (those that ``bench/limits/<cell>.json`` names are compared, each
against its limit there; the others are readings):

- ``prob_gap``: the widest absolute gap between the probabilities and
  deferral probabilities that the first tick's recorded route passes
  (64 lanes, the seed's weights) returned and the reference's on the
  same rows.
- ``grad_gap.level<i>``: the gap between the program's and the
  reference's norms of level ``i``'s first student gradient (from the
  optimizer's state after the first tick), by the whole tree, against
  the reference's.
- ``route_gap``: the widest margin, in probability, by which an answer's
  routing (defer or exit at each level it reached) or class lies on the
  wrong side by the reference; 1.0 where the answer is not one the
  cascade could give (a DAgger jump not taken, a called item not
  answered with its label).
- ``label_gap``: the widest gap by which the expert logit of a served
  label lies below the reference expert's best.
- ``delta_gap``: each leaf's gap between the norms of the parameters'
  change over the replayed ticks, against the reference's norm of that
  leaf or the median leaf's, whichever is larger; the median leaf's gap
  of each level's student and deferral tree, the largest.  Leaves whose
  reference gradient is under a thousandth of the median leaf's are left
  out.
- ``feature_mismatch``: recorded input rows that are not the
  featurisation of one of the tick's documents (exact: limit 0).

The reference is teacher-forced on the program's discrete outputs (which
lanes were called, the labels served), as a served model's reference is
on its served tokens, and measures the gap of each.  ``control`` puts the
reference computed one precision lower in the program's place.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import kinds
import model

B1, B2, EPS = 0.9, 0.999, 1e-8
BLOCK = 16                      # reference rows per call


# -- precision -------------------------------------------------------------
class Precision:
    """How the reference computes: float32 at full matmul precision (or
    at ``matmul``, for a witness); or, as the control, one step below the
    matmul precision that a level states: float8 e4m3 operands
    (per-tensor scale) where it states bfloat16 operands (the TPU's
    default precision over float32 arrays), three bfloat16 passes
    (``high``) where it states ``highest``."""

    BELOW = {"bfloat16": ("highest", "fp8"), "highest": ("high", None)}

    def __init__(self, control: bool, matmul: str = "highest"):
        self.control = control
        self.matmul = matmul

    def of(self, stated: str):
        """``(matmul precision, operand rounding)`` for a level."""
        if self.control:
            return self.BELOW[stated]
        return self.matmul, None


def _forward(level, prec, student, dparams, x):
    matmul, rounding = prec.of(level["precision"])
    x = np.asarray(x)
    n = x.shape[0]
    x = np.concatenate([x, np.zeros((-n % BLOCK,) + x.shape[1:], x.dtype)])
    outs_p, outs_d = [], []
    with jax.default_matmul_precision(matmul):
        for lo in range(0, x.shape[0], BLOCK):
            p, d = model.level_forward(level, student, dparams,
                                       x[lo:lo + BLOCK], rounding)
            outs_p.append(np.asarray(p, np.float32))
            outs_d.append(np.asarray(d, np.float32))
    return np.concatenate(outs_p)[:n], np.concatenate(outs_d)[:n]


def _expert(casc, prec, params, docs):
    spec = casc["expert"]
    matmul, rounding = prec.of(spec["precision"])
    n = len(docs)
    if n == 0:
        return np.zeros((0, casc["n_classes"]), np.float32)
    shape, dtype = kinds.reference(spec["kind"]).row(spec,
                                                     casc["n_features"])
    x = np.concatenate([model.rows(spec["kind"], spec, docs,
                                   casc["n_features"]),
                        np.zeros((-n % 64,) + shape, dtype)])
    out = []
    with jax.default_matmul_precision(matmul):
        for lo in range(0, len(x), 64):
            out.append(np.asarray(model.expert_logits(
                spec, params, x[lo:lo + 64], rounding), np.float32))
    return np.concatenate(out)[:n]


# -- the gaps --------------------------------------------------------------
def label_gap(logits: np.ndarray, labels: np.ndarray) -> float:
    """Widest gap of served labels' logits below the best."""
    if labels.size == 0:
        return 0.0
    return float(np.max(logits.max(-1)
                        - logits[np.arange(labels.size), labels]))


def feature_mismatch(calls, feats_by_tick: Dict[int, List[np.ndarray]]) -> int:
    """Recorded non-pad rows that are no featurised document of their tick."""
    bad = 0
    for t, i, xb, _ in calls:
        known = {r.tobytes() for r in feats_by_tick[t][i]}
        x = np.asarray(xb)
        for row in x:
            if np.any(row) and row.tobytes() not in known:
                bad += 1
    return bad


def walk_gaps(levels_prog, preds_prog, probs, dprob, jumps=None,
              called=None):
    """Gaps of one tick's answers against reference probabilities.

    ``probs``: (nlev, S, C) and ``dprob``: (nlev, S) of the reference;
    ``jumps``: (nlev, S) DAgger draws taken (None: no jumps); ``called``:
    the program's expert-called mask (None: never called).  Returns
    ``(gap, called_expected)``."""
    nlev, S = dprob.shape
    gap = 0.0
    alive = np.ones(S, bool)
    jumped = np.zeros(S, bool)
    for i in range(nlev):
        if jumps is not None:
            jumped |= alive & jumps[i]
            alive &= ~jumps[i]
        for s in np.flatnonzero(alive):
            exit_here = levels_prog[s] == i
            if exit_here:
                gap = max(gap, float(dprob[i, s]) - 0.5)
                p = probs[i, s]
                gap = max(gap, float(p.max() - p[preds_prog[s]]))
                alive[s] = False
            else:
                gap = max(gap, 0.5 - float(dprob[i, s]))
    expected = jumped | alive
    if called is not None and np.any(expected != called):
        gap = 1.0
    if called is None and np.any(expected):
        gap = 1.0
    return max(gap, 0.0), expected


def leaf_gaps(prog: List[float], ref: List[float],
              gref: List[float]) -> np.ndarray:
    """Each leaf's gap of norms (see the module docstring); NaN where the
    reference's gradient leaves the leaf out."""
    gref = np.asarray(gref, np.float64)
    ref = np.asarray(ref, np.float64)
    gaps = np.abs(np.asarray(prog) - ref) / np.maximum(ref, np.median(ref))
    gaps[gref < 1e-3 * np.median(gref)] = np.nan
    return gaps


def leaf_gap(prog: List[float], ref: List[float], gref: List[float]) -> float:
    """Worst leaf's gap of norms."""
    g = leaf_gaps(prog, ref, gref)
    return float(np.nanmax(g)) if np.isfinite(g).any() else 0.0


def change_gaps(prog_deltas, ref_deltas, ref_grads, names):
    """The parameters' change against the reference's, per level and
    tree: the median leaf's gap (compared), and the worst leaf's gap with
    its name (a reading).  Returns ``(median, worst, where)``."""
    median, worst, where = 0.0, 0.0, ""
    for i, (pds, rds, rgs) in enumerate(zip(prog_deltas, ref_deltas,
                                           ref_grads)):
        for k, tree in enumerate(("student", "deferral")):
            g = leaf_gaps(pds[k], rds[k], rgs[k])
            if not np.isfinite(g).any():
                continue
            median = max(median, float(np.nanmedian(g)))
            j = int(np.nanargmax(g))
            if g[j] > worst:
                worst, where = float(g[j]), f"level {i} {tree}{names[i][k][j]}"
    return median, worst, where


def leaf_names(weights) -> list:
    """Per level, the student's and the deferral gate's leaf paths."""
    return [tuple([jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(w[tree])[0]]
                  for tree in ("student", "deferral"))
            for w in weights["levels"]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b, scale):
    return [jnp.sqrt(jnp.sum(jnp.square((x - y) * scale)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


@jax.jit
def _scaled(a, b, scale):
    if b is None:
        return jax.tree.map(lambda x: x * scale, a)
    return jax.tree.map(lambda x, y: (x - y) * scale, a, b)


def scaled_diff(a, b, scale: float):
    """``(a - b) * scale`` leaf by leaf (``a * scale`` when ``b`` is None),
    on the device, in one call."""
    return _scaled(a, b, jnp.float32(scale))


def tree_gap(prog: List[float], ref: List[float]) -> float:
    """Gap of a whole tree's norms, from its leaves' norms, against the
    reference's."""
    p = math.sqrt(sum(x * x for x in prog))
    r = math.sqrt(sum(x * x for x in ref))
    return abs(p - r) / r if r > 0 else (0.0 if p == 0 else math.inf)


def tree_norms(tree) -> List[float]:
    """Per-leaf l2 norms, in leaf order."""
    return [float(x) for x in jax.device_get(_norms(tree))]


def diff_norms(a, b, scale: float = 1.0) -> List[float]:
    """Per-leaf l2 norms of ``(a - b) * scale``, in leaf order."""
    return [float(x) for x in jax.device_get(
        _diff_norms(a, b, jnp.float32(scale)))]


# -- the learning replay ---------------------------------------------------
def _xent(logits, labels, w):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - gold) * w) / jnp.maximum(jnp.sum(w), 1.0)


@functools.lru_cache(maxsize=None)
def _student_grad_fn(kind: str, spec_items, rounding):
    fn = kinds.reference(kind).logits
    spec = dict(spec_items) if spec_items else None
    rnd = model.ROUNDINGS[rounding]

    def loss(q, xb, yb):
        return _xent(fn(q, xb, spec, rnd), yb, jnp.ones(yb.shape, jnp.float32))
    return jax.jit(jax.grad(loss))


def _student_grad(level, prec, p, xb, yb):
    matmul, rounding = prec.of(level["precision"])
    spec = level.get("spec")
    f = _student_grad_fn(level["kind"],
                         tuple(sorted(spec.items())) if spec else None,
                         rounding)
    with jax.default_matmul_precision(matmul):
        return f(p, jnp.asarray(xb), jnp.asarray(yb, jnp.int32))


@functools.lru_cache(maxsize=None)
def _deferral_grad_fn(cf: float, rounding):
    rnd = model.ROUNDINGS[rounding]

    def loss(q, probs, y, reach, mu_dc):
        f = model.deferral_prob(q, probs, rnd)
        z = (jnp.argmax(probs, -1) != y).astype(jnp.float32)
        p_y = jnp.take_along_axis(probs, y[:, None], axis=-1)[:, 0]
        mcl = mu_dc + jnp.log(jnp.maximum(p_y, 1e-9))
        n = jnp.maximum(jnp.float32(probs.shape[0]), 1.0)
        mse = jnp.sum(jnp.square(f - z)) / n
        cost = jnp.sum(reach * f * mcl) / n
        return cf * mse + (1.0 - cf) * cost
    return jax.jit(jax.grad(loss))


def _deferral_grad(level, prec, dp, probs, y, reach, mu_dc):
    matmul, rounding = prec.of(level["precision"])
    f = _deferral_grad_fn(float(level["calibration_factor"]), rounding)
    with jax.default_matmul_precision(matmul):
        return f(dp, probs, y, reach, mu_dc)


@jax.jit
def _adam_jit(p, g, m, v, t, lr):
    tf = t.astype(jnp.float32)
    m = jax.tree.map(lambda m0, gg: B1 * m0 + (1 - B1) * gg.astype(jnp.float32),
                     m, g)
    v = jax.tree.map(lambda v0, gg: B2 * v0 + (1 - B2) * jnp.square(
        gg.astype(jnp.float32)), v, g)
    bc1 = 1 - B1 ** tf
    bc2 = 1 - B2 ** tf
    p = jax.tree.map(lambda a, m_, v_: a - lr * (m_ / bc1)
                     / (jnp.sqrt(v_ / bc2) + EPS), p, m, v)
    return p, m, v


def _adam(p, g, st, lr):
    t = st["count"] + 1
    p, m, v = _adam_jit(p, g, st["m"], st["v"], jnp.int32(t),
                        jnp.float32(lr))
    return p, {"count": t, "m": m, "v": v}


@jax.jit
def _ogd_jit(p, g, eta):
    return jax.tree.map(lambda a, gg: a - eta * gg.astype(jnp.float32), p, g)


def _ogd(p, g, st, lr):
    t = st["count"] + 1
    return _ogd_jit(p, g, jnp.float32(lr / math.sqrt(t))), {"count": t}


def _zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _opt_state(optimizer: str, p):
    if optimizer == "ogd":
        return {"count": 0}
    return {"count": 0, "m": _zeros_like(p), "v": _zeros_like(p)}


STEPS = {"ogd": _ogd, "adam": _adam}


def tick_draws(seed: int, S: int, t: int, nlev: int):
    """Lane DAgger uniforms (nlev, S) and lane 0's cache generators."""
    u = np.empty((nlev, S))
    cache = None
    for s in range(S):
        ch = np.random.SeedSequence(
            ((seed % 2 ** 31) & 0x7FFFFFFF, s, t)).spawn(2 + nlev)
        u[:, s] = np.random.default_rng(ch[0]).random(nlev)
        if s == 0:
            cache = [np.random.default_rng(c) for c in ch[2:]]
    return u, cache


def sample_cache(rng, n: int, bs: int) -> np.ndarray:
    """Mini-batch indices over a ring holding ``n`` items."""
    if n < bs:
        return rng.integers(0, n, size=bs)
    return rng.choice(n, size=bs, replace=False)


def replay_learning(cfg: dict, mix: dict, seed: int, weights, docs_by_tick,
                    outs_by_tick, prec: Precision, calls=None,
                    keep_grads: bool = False) -> dict:
    """Follow the program's first ticks with the plain algorithm.

    Returns the reference's (or the control's) readings: per tick the
    walk's probabilities, the expert's logits on called items, the
    recorded route passes recomputed at the tick's state, the first
    gradient's leaf norms and the parameter change's leaf norms."""
    casc = cfg["cascade"]
    levels = casc["levels"]
    nlev = len(levels)
    S = mix["lanes"]
    C = casc["n_classes"]
    nf = casc["n_features"]
    st = []
    for lv, w in zip(levels, weights["levels"]):
        kind = kinds.reference(lv["kind"])
        shape, dtype = kind.row(lv.get("spec"), nf)
        st.append({
            "p": w["student"], "dp": w["deferral"],
            "step": STEPS[kind.OPTIMIZER],
            "opt": _opt_state(kind.OPTIMIZER, w["student"]),
            "dopt": _opt_state("adam", w["deferral"]),
            "cx": np.zeros((lv["cache_size"],) + shape, dtype),
            "cy": np.zeros((lv["cache_size"],), np.int32), "n": 0, "ptr": 0,
            "beta": casc["beta0"]})
    res = {"ticks": [], "calls": [], "grad_norms": None, "batches": [],
           "first_grads": []}
    for t in sorted(docs_by_tick):
        docs = docs_by_tick[t]
        out = outs_by_tick[t]
        feats = [model.featurize(lv, docs, nf) for lv in levels]
        u, cache_rngs = tick_draws(seed, S, t, nlev)
        jumps = u < np.array([s_["beta"] for s_ in st])[:, None]
        probs = np.zeros((nlev, S, C), np.float32)
        dprob = np.zeros((nlev, S), np.float32)
        for i, lv in enumerate(levels):
            probs[i], dprob[i] = _forward(lv, prec, st[i]["p"], st[i]["dp"],
                                          feats[i])
        if calls is not None:
            for (tc, i, xb, _) in calls:
                if tc == t:
                    res["calls"].append((t, i, _forward(
                        levels[i], prec, st[i]["p"], st[i]["dp"],
                        np.asarray(xb))))
        called = np.asarray(out["expert_called"], bool)
        y = np.asarray(out["expert_labels"], np.int64)
        sel = np.flatnonzero(called)
        elog = _expert(casc, prec, weights["expert"],
                       [docs[s] for s in sel])
        res["ticks"].append({"probs": probs, "dprob": dprob, "jumps": jumps,
                             "expert_logits": elog})
        k = sel.size
        grads = []
        for i, lv in enumerate(levels):
            s_ = st[i]
            size = lv["cache_size"]
            if k:
                order = np.cumsum(called) - 1
                keep = called & (order >= k - size)
                slots = (s_["ptr"] + order[keep]) % size
                s_["cx"][slots] = feats[i][keep]
                s_["cy"][slots] = y[keep]
                s_["n"] = min(s_["n"] + k, size)
                s_["ptr"] = (s_["ptr"] + k) % size
                bs = min(lv["batch_size"], size)
                idx = sample_cache(cache_rngs[i], s_["n"], bs)
                g = _student_grad(lv, prec, s_["p"], s_["cx"][idx],
                                  s_["cy"][idx])
                s_["p"], s_["opt"] = s_["step"](s_["p"], g, s_["opt"],
                                                lv["student_lr"])
                reach = np.ones(k, np.float32)
                for j in range(i):
                    reach = reach * dprob[j, sel]
                mu_dc = mix["mu"] * (levels[i + 1]["cost"] if i + 1 < nlev
                                     else casc["expert_cost"])
                dg = _deferral_grad(lv, prec, s_["dp"],
                                    jnp.asarray(probs[i, sel]),
                                    jnp.asarray(y[sel], jnp.int32),
                                    jnp.asarray(reach), np.float32(mu_dc))
                s_["dp"], s_["dopt"] = _adam(s_["dp"], dg, s_["dopt"],
                                             lv["deferral_lr"] * 20)
                grads.append((tree_norms(g), tree_norms(dg)))
                if res["grad_norms"] is None:
                    res["batches"].append((s_["cx"][idx], s_["cy"][idx]))
                    if keep_grads:
                        res["first_grads"].append(g)
            s_["beta"] = max(s_["beta"] * lv["beta_decay"] ** S,
                             lv["beta_floor"] / math.sqrt(max(t * S, 1)))
        if res["grad_norms"] is None:
            res["grad_norms"] = grads
    res["delta_norms"] = [
        (diff_norms(s_["p"], w["student"]), diff_norms(s_["dp"], w["deferral"]))
        for s_, w in zip(st, weights["levels"])]
    return res


def _say(msg: str) -> None:
    import sys
    print(msg, file=sys.stderr, flush=True)


def learning_numbers(cfg, mix, seed, weights, docs_by_tick, outs_by_tick,
                     calls, prog_grads, prog_deltas, window_labels,
                     control: bool = False):
    """The learning cell's compared numbers (see the module docstring),
    and the readings of planted faults that set their upper limits.

    ``prog_grads``/``prog_deltas``: per level ``(student, deferral)`` leaf
    norms read from the program's state; ``window_labels``: the window's
    sampled ``(docs, labels)`` of called items.  With ``control`` the
    reference one precision lower stands in for the program.  Returns
    ``(numbers, readings)``: the compared numbers, and the readings that
    are not compared or that set upper limits (planted faults, read
    against the reference)."""
    casc = cfg["cascade"]
    levels = casc["levels"]
    nf = casc["n_features"]
    ticks = sorted(docs_by_tick)
    ref = replay_learning(cfg, mix, seed, weights, docs_by_tick, outs_by_tick,
                          Precision(False), calls)
    if control:
        low = replay_learning(cfg, mix, seed, weights, docs_by_tick,
                              outs_by_tick, Precision(True), calls)
        got_calls = [c[2] for c in low["calls"]]
        prog_grads, prog_deltas = low["grad_norms"], low["delta_norms"]
    else:
        got_calls = [tuple(np.asarray(a, np.float32) for a in out)
                     for (_, _, _, out) in calls]
    # the first tick's route passes run on the seed's weights, exactly the
    # reference's state: compared.  Later ticks' states differ by Adam's
    # first step, about lr * sign(g) on every parameter, whose signs two
    # sound precisions set apart where g is near nought: a reading
    prob, later, per = 0.0, 0.0, {}
    for (gp, gd), (t, i, (rp, rd)) in zip(got_calls, ref["calls"]):
        g = max(float(np.max(np.abs(gp - rp))), float(np.max(np.abs(gd - rd))))
        per[(t, i)] = max(per.get((t, i), 0.0), g)
        if t == ticks[0]:
            prob = max(prob, g)
        else:
            later = max(later, g)
    _say("prob gap by (tick, level): " + ", ".join(
        f"{k}: {v}" for k, v in sorted(per.items())))
    route, lab, flip_route = 0.0, 0.0, 0.0
    for t, tk in zip(ticks, ref["ticks"]):
        out = outs_by_tick[t]
        called = np.asarray(out["expert_called"], bool)
        levels_p = np.asarray(out["levels"])
        preds_p = np.asarray(out["predictions"])
        labels = np.asarray(out["expert_labels"])[called]
        if control:
            lowt = low["ticks"][ticks.index(t)]
            # the control's own first choices, scored by the reference
            preds_p = preds_p.copy()
            exit_lv = np.minimum(levels_p, len(levels) - 1)
            for s in np.flatnonzero(~called):
                preds_p[s] = int(np.argmax(lowt["probs"][exit_lv[s], s]))
            labels = np.argmax(lowt["expert_logits"], -1)
            g, _ = walk_gaps(levels_p, preds_p, tk["probs"], tk["dprob"],
                             tk["jumps"], called)
            for i in range(len(levels)):
                flips = (lowt["dprob"][i] > 0.5) != (tk["dprob"][i] > 0.5)
                if flips.any():
                    g = max(g, float(np.max(np.abs(tk["dprob"][i][flips]
                                                   - 0.5))))
        else:
            g, _ = walk_gaps(levels_p, preds_p, tk["probs"], tk["dprob"],
                             tk["jumps"], called)
            if np.any(preds_p[called] != labels):
                g = 1.0
        f, _ = walk_gaps(levels_p, 1 - preds_p, tk["probs"], tk["dprob"],
                         tk["jumps"], called)
        # an altered answer of a called item is not its label: gap 1
        flip_route = max(flip_route, 1.0 if called.any() else f)
        route = max(route, g)
        lab = max(lab, label_gap(tk["expert_logits"], labels))
    docs_w, labels_w = window_labels
    flip_label = 0.0
    if docs_w:
        ref_l = _expert(casc, Precision(False), weights["expert"], docs_w)
        if control:
            labels_w = np.argmax(_expert(casc, Precision(True),
                                         weights["expert"], docs_w), -1)
        lab = max(lab, label_gap(ref_l, np.asarray(labels_w)))
        flip_label = label_gap(ref_l, 1 - np.asarray(labels_w))
    # the first gradient of each level's student, by its whole tree's
    # norm (the limits name the levels compared); the deferral gate's,
    # whose error indicator 1[argmax != label] jumps where two classes
    # tie, and the worst leaf are readings
    grad = {f"grad_gap.level{i}": tree_gap(pg, rg)
            for i, ((pg, _), (rg, _))
            in enumerate(zip(prog_grads, ref["grad_norms"]))}
    grad_dfr = max(tree_gap(pdg, rdg)
                   for (_, pdg), (_, rdg) in zip(prog_grads, ref["grad_norms"]))
    grad_leaf = max(max(leaf_gap(pg, rg, rg), leaf_gap(pdg, rdg, rdg))
                    for (pg, pdg), (rg, rdg)
                    in zip(prog_grads, ref["grad_norms"]))
    # the change over the replayed ticks by each tree's median leaf: Adam
    # moves every element by about lr * sign(g) a step, so a small leaf
    # of the deferral gate whose gradient signs two sound precisions set
    # apart at a tie reads far off alone (PERF.md); the worst leaf, named,
    # is a reading
    delta, delta_worst, delta_where = change_gaps(
        prog_deltas, ref["delta_norms"], ref["grad_norms"],
        leaf_names(weights))
    # a planted fault, read against the reference: the first student step
    # on half of its batch
    half = {}
    for i, (lv, (xb, yb), (rg, _), w) in enumerate(zip(
            levels, ref["batches"], ref["grad_norms"], weights["levels"])):
        n = len(yb) // 2
        gh = _student_grad(lv, Precision(False), w["student"], xb[:n], yb[:n])
        half[f"grad_gap.level{i}.half_batch"] = tree_gap(tree_norms(gh), rg)
    feats_by_tick = {t: [model.featurize(lv, d, nf) for lv in levels]
                     for t, d in docs_by_tick.items()}
    numbers = {"prob_gap": prob, **grad, "route_gap": route,
               "label_gap": lab, "delta_gap": delta,
               "feature_mismatch": float(feature_mismatch(calls, feats_by_tick))
               if not control else 0.0}
    faults = {"prob_gap.later_ticks": later, "grad_gap.deferral": grad_dfr,
              "grad_gap.worst_leaf": grad_leaf,
              "delta_gap.worst_leaf": delta_worst,
              "delta_gap.worst_leaf_is": delta_where,
              **half, "delta_gap.unchanged_state": 1.0,
              "route_gap.altered_answer": flip_route,
              "label_gap.altered_label": flip_label}
    return numbers, faults


@jax.jit
def _sign_flips(a, b):
    flips = moved = jnp.float32(0.0)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        live = (x != 0) | (y != 0)
        flips += jnp.sum(live & (jnp.sign(x) != jnp.sign(y)))
        moved += jnp.sum(live)
    return flips / jnp.maximum(moved, 1.0)


def witness_readings(cfg, mix, seed, weights, docs_by_tick, outs_by_tick,
                     calls) -> dict:
    """Sound replays at lower matmul precisions (``high``: three bfloat16
    passes; ``default``: one, as the TPU runs the program) against the
    reference: each replayed tick's widest route-pass gap, and the share
    of each level's first student gradient whose signs differ from the
    reference's (Adam's first step is about ``lr * sign(g)``)."""
    ref = replay_learning(cfg, mix, seed, weights, docs_by_tick, outs_by_tick,
                          Precision(False), calls, keep_grads=True)
    out = {}
    for matmul in ("high", "default"):
        wit = replay_learning(cfg, mix, seed, weights, docs_by_tick,
                              outs_by_tick, Precision(False, matmul), calls,
                              keep_grads=True)
        by_tick = {}
        for (t, _, (wp, wd)), (_, _, (rp, rd)) in zip(wit["calls"],
                                                      ref["calls"]):
            by_tick[t] = max(by_tick.get(t, 0.0),
                             float(np.max(np.abs(wp - rp))),
                             float(np.max(np.abs(wd - rd))))
        out[f"witness.{matmul}.prob_gap_by_tick"] = by_tick
        out[f"witness.{matmul}.sign_flip_share"] = [
            float(_sign_flips(a, b))
            for a, b in zip(wit["first_grads"], ref["first_grads"])]
        out[f"witness.{matmul}.grad_gap"] = [
            (tree_gap(w[0], r[0]), tree_gap(w[1], r[1]))
            for w, r in zip(wit["grad_norms"], ref["grad_norms"])]
        out[f"witness.{matmul}.delta_gap"] = change_gaps(
            wit["delta_norms"], ref["delta_norms"], ref["grad_norms"],
            leaf_names(weights))
        del wit
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            n_calls: int) -> bool:
    """``correct``: every number that the cell's limits name is there and
    within its limit, and something was recorded.  Numbers the limits do
    not name are readings."""
    if n_calls == 0 or not limits:
        return False
    for k, lim in limits.items():
        v = numbers.get(k, math.nan)
        if not math.isfinite(v) or v > lim:
            return False
    return True
