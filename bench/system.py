"""The system under test: the repo's engine, built from a configuration
file and a traffic mix, with the benchmark's weights installed.

With the kinds' adapters (``bench/kinds/<kind>_program.py``), this is the
only file of the benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import kinds


def build(cascade: dict, mix: dict, seed: int, weights):
    """A ``BatchedCascadeEngine`` for this cell, its weights replaced by
    ``weights`` (``model.make_weights``); each level's and the expert's
    program objects come from their kinds' adapters
    (``bench/kinds/<kind>_program.py``)."""
    from repro.core.batched import BatchedCascadeEngine
    from repro.core.cascade import CascadeConfig, LevelSpec

    C = cascade["n_classes"]
    fields = {f.name for f in dataclasses.fields(LevelSpec)}
    specs = {}
    for lv in cascade["levels"]:
        specs.update(kinds.program(lv["kind"]).config(lv.get("spec"), C))
    cfg = CascadeConfig(
        levels=tuple(LevelSpec(**{k: v for k, v in lv.items() if k in fields})
                     for lv in cascade["levels"]),
        n_classes=C, expert_cost=cascade["expert_cost"], mu=mix["mu"],
        beta0=cascade["beta0"], n_features=cascade["n_features"],
        sample_actions=cascade["sample_actions"],
        hard_budget=mix["hard_budget"], seed=seed % (2 ** 31), **specs)
    ex = cascade["expert"]
    expert = kinds.program(ex["kind"]).build(weights["expert"], ex, C,
                                             cascade["expert_cost"])
    eng = BatchedCascadeEngine(
        cfg, expert, n_streams=mix["lanes"],
        updates_per_tick=mix["updates_per_tick"], max_delay=mix["max_delay"],
        pipeline_depth=mix["pipeline_depth"], history_limit=0,
        commit_log=False)
    for lvl, w in zip(eng.levels, weights["levels"]):
        for attr, tree in (("params", w["student"]),
                           ("dparams", w["deferral"])):
            have = jax.tree.map(lambda a: (a.shape, a.dtype),
                                getattr(lvl, attr))
            want = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
            if jax.tree.structure(getattr(lvl, attr)) != \
                    jax.tree.structure(tree) or have != want:
                raise RuntimeError(
                    f"level {lvl.spec.kind}: the program's {attr} tree no "
                    f"longer has the layout the benchmark draws")
            setattr(lvl, attr, tree)
        lvl._init_state = (lvl.params, lvl.opt_state, lvl.dparams,
                           lvl.dopt_state)
    return eng


class RouteRecorder:
    """Keeps the inputs and outputs of the route passes of chosen ticks.

    Wraps each level's compiled route pass; while ``on`` is set, every
    call's ``(tick, level, rows, (probs, dprob))`` is kept, as the device
    arrays the window itself produced."""

    def __init__(self, eng):
        self.on = False
        self.tick = 0
        self.calls = []
        for i, f in enumerate(eng._predict_defer):
            eng._predict_defer[i] = self._wrap(i, f)

    def _wrap(self, i, f):
        def route(params, dparams, xb):
            out = f(params, dparams, xb)
            if self.on:
                self.calls.append((self.tick, i, xb, out))
            return out
        return route


def warm_shapes(eng) -> None:
    """Compile each level's route pass at every lane bucket the cell's
    ticks can hand it, without changing the engine's state.  The tick's
    update program and the expert's forward at each batch size are
    compiled by the ticks and expert calls of ``drivers.Run.warm``."""
    S = eng.n_streams
    buckets = sorted({eng._bucket(n) for n in range(1, S + 1)})
    outs = []
    for i, lvl in enumerate(eng.levels):
        shape = lvl.cache_x.shape[1:]
        dtype = lvl.cache_x.dtype
        for b in buckets:
            outs.append(eng._predict_defer[i](
                lvl.params, lvl.dparams, jnp.zeros((b,) + shape, dtype)))
    jax.block_until_ready(outs)


def state_trees(eng):
    """The learned state the update pass writes, per level."""
    return [{"params": lvl.params, "opt_state": lvl.opt_state,
             "dparams": lvl.dparams, "dopt_state": lvl.dopt_state}
            for lvl in eng.levels]

