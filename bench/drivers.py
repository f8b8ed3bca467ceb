"""The load: set-up ticks, warm-up, and the measured window.

``lockstep`` (the one driver): every lane takes its next document when
the tick returns (closed loop, ``process_tick`` as ``engine.run`` drives
it).

Host spans (``jax.profiler.TraceAnnotation``) named ``bench.<what>`` mark
each call into the engine, on the profiler's clock.
"""
from __future__ import annotations

import time
import numpy as np

import check
import kinds
import system


class Run:
    """One run's engine, traffic and the evidence kept for the check."""

    def __init__(self, eng, rec, mix, docs, seed, jax):
        self.eng, self.rec, self.mix, self.docs = eng, rec, mix, docs
        self.seed = seed
        self.jax = jax
        self.S = mix["lanes"]
        self.cursor = 0
        self.docs_by_tick, self.outs_by_tick = {}, {}
        self.prog_grads = self.prog_deltas = None
        self.window_labels = ([], [])
        self.span = jax.profiler.TraceAnnotation

    # -- traffic ---------------------------------------------------------
    def next_tick(self):
        idxs = [(self.cursor + s) % len(self.docs) for s in range(self.S)]
        self.cursor += self.S
        return idxs, [self.docs[i] for i in idxs]

    # -- set-up ----------------------------------------------------------
    def replay_ticks(self, n: int, weights, casc: dict) -> None:
        """The cell's first ``n`` ticks, recorded for the replay, with
        the program's first-gradient and parameter-change leaf norms."""
        eng = self.eng
        for t in range(1, n + 1):
            idxs, docs = self.next_tick()
            self.rec.on, self.rec.tick = True, eng.t + 1
            out = eng.process_tick(idxs, docs)
            self.rec.on = False
            self.docs_by_tick[out["tick"]] = docs
            self.outs_by_tick[out["tick"]] = out
            if t == 1:
                self.prog_grads = []
                for lv, lvl, w in zip(casc["levels"], eng.levels,
                                      weights["levels"]):
                    dg = check.scaled_diff(lvl.dopt_state["m"], None,
                                           1 / (1 - check.B1))
                    if kinds.reference(lv["kind"]).OPTIMIZER == "ogd":
                        # OGD's first step is -lr * g
                        g = check.scaled_diff(lvl.params, w["student"],
                                              -1.0 / lv["student_lr"])
                    else:
                        g = check.scaled_diff(lvl.opt_state["m"], None,
                                              1 / (1 - check.B1))
                    self.prog_grads.append((check.tree_norms(g),
                                            check.tree_norms(dg)))
        self.prog_deltas = [
            (check.diff_norms(lvl.params, w["student"]),
             check.diff_norms(lvl.dparams, w["deferral"]))
            for lvl, w in zip(eng.levels, weights["levels"])]

    def warm(self, compiles) -> None:
        """Compile the cell's shapes, then run ticks of its own traffic
        until two in a row compile nothing."""
        system.warm_shapes(self.eng)
        for k in range(1, self.S + 1):
            self.eng.expert.label_batch(list(range(k)), self.docs[:k])
        quiet = 0
        for _ in range(24):
            n0 = compiles.n
            idxs, docs = self.next_tick()
            self.eng.process_tick(idxs, docs)
            quiet = quiet + 1 if compiles.n == n0 else 0
            if quiet >= 2:
                break
        self.jax.block_until_ready(system.state_trees(self.eng))

    # -- windows ---------------------------------------------------------
    def window_lockstep(self, seconds: float) -> dict:
        """Closed loop for ``seconds``; items answered per second."""
        chk = self.mix["check"]
        rng = np.random.default_rng([self.seed % 2 ** 63, 7])
        label_ticks = set(rng.choice(64, chk.get("label_ticks", 0),
                                     replace=False).tolist())
        items, k, called = 0, 0, []
        tick_levels, tick_called = [], []
        t0 = time.perf_counter()
        with self.span("bench.window"):
            while True:
                idxs, docs = self.next_tick()
                with self.span("bench.tick"):
                    out = self.eng.process_tick(idxs, docs)
                items += len(docs)
                c = np.asarray(out["expert_called"], bool)
                called.append((time.perf_counter() - t0, int(c.sum())))
                if k in label_ticks and c.any():
                    sel = np.flatnonzero(c)
                    self.window_labels[0].extend(docs[s] for s in sel)
                    self.window_labels[1].extend(
                        int(x) for x in out["expert_labels"][sel])
                tick_levels.append(out["levels"])
                tick_called.append(c)
                k += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            with self.span("bench.sync"):
                self.jax.block_until_ready(system.state_trees(self.eng))
        dt = time.perf_counter() - t0
        quarters = np.zeros((4, 2))
        for ts, n in called:
            quarters[min(int(4 * ts / dt), 3)] += (n, self.S)
        return {"e2e": {"items_per_s": items / dt},
                "attempted": items, "failed": 0, "ticks": k, "seconds": dt,
                "tick_levels": tick_levels, "tick_called": tick_called,
                "info": {"ticks": k, "window_s": dt,
                         "expert_call_fraction_by_quarter": [
                             float(a / max(b, 1)) for a, b in quarters]}}

    # -- evidence --------------------------------------------------------
    def evidence(self) -> dict:
        """What the check needs once the engine is gone."""
        return {"docs_by_tick": self.docs_by_tick,
                "outs_by_tick": self.outs_by_tick,
                "calls": [x for x in self.rec.calls
                          if x[0] in self.docs_by_tick],
                "prog_grads": self.prog_grads,
                "prog_deltas": self.prog_deltas,
                "window_labels": self.window_labels}
