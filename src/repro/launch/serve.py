"""Streaming cascade server — the paper's deployment shape (serving kind).

Two engines:

* ``--engine batched`` (default): ``BatchedCascadeEngine`` serves S
  concurrent stream lanes in lockstep — per-level batched student
  forwards over the gathered alive subset, one batched expert forward per
  tick for the deferred lanes, and per-tick weighted student/deferral
  updates (see core/batched.py for the RNG/equivalence contract).
* ``--engine sequential``: the per-item Algorithm-1 reference loop, with
  micro-batched expert calls via a probe/replay pass (the pre-batched
  serving path, kept for comparison and as the semantics oracle).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --dataset hatespeech \
      --samples 2000 --mu 3e-7 --batch 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as _san
from repro.core import (BatchedCascadeEngine, OnlineCascade, SimulatedExpert,
                        default_cascade_config)
from repro.core.experts import train_model_expert
from repro.core.rng import tick_rngs
from repro.launch.compile_cache import enable_compile_cache


class _BatchProxy:
    """Expert proxy serving precomputed labels to the cascade during the
    replay pass of a micro-batch; falls back to a single expert call when
    the routing probe mispredicted (rare: post-update gate flips)."""

    def __init__(self, expert):
        self.expert = expert
        self.cost = expert.cost
        self.table = {}
        self.fallback_calls = 0

    def label(self, idx: int, doc) -> int:
        """Serve item ``idx``'s precomputed label (or fall back live)."""
        if idx in self.table:
            return int(self.table[idx])
        self.fallback_calls += 1
        return int(self.expert.label(idx, doc))


def probe_route(cascade: OnlineCascade, doc, tick: int) -> bool:
    """Predict whether processing ``doc`` at ``tick`` would consult the
    expert, WITHOUT mutating cascade state.  The per-tick pre-split RNG
    discipline (core.rng) lets the probe reproduce the exact DAgger jump
    draws — and, under ``cfg.sample_actions``, the exact sampled-action
    draws — that the replay pass will see.  (The probe previously always
    thresholded dprob at 0.5; with sampled actions that mispredicted the
    route whenever the draw disagreed with the threshold, degrading the
    micro-batch to single-call expert fallbacks.)"""
    cfg = cascade.cfg
    n_levels = len(cascade.levels)
    rngs = tick_rngs(cfg.seed, cascade.stream_id, tick, n_levels)
    u_jump = rngs.jump.random(n_levels)
    u_act = rngs.action.random(n_levels) if cfg.sample_actions else None
    for i, lvl in enumerate(cascade.levels):
        if not cascade._budget_exhausted() and u_jump[i] < lvl.beta:
            return True                      # DAgger jump
        x = lvl.featurize(doc)
        _, dprob = lvl._predict_and_defer(
            lvl.params, lvl.dparams, jnp.asarray(x))
        if cfg.sample_actions:
            # float32 comparison, identical to OnlineCascade.process
            defer = float(np.float32(u_act[i])) < float(dprob)
        else:
            defer = float(dprob) > 0.5
        if cascade._budget_exhausted() and i == n_levels - 1:
            defer = False
        if not defer:
            return False
    return True


def _make_expert(stream, n_classes, expert_kind, samples, seed,
                 workers=1, backend: str = "thread"):
    if expert_kind == "model":
        print("training stand-in LLM expert ...", flush=True)
        return train_model_expert(stream, n_classes, epochs=2,
                                  max_samples=min(4000, samples), seed=seed,
                                  workers=workers, backend=backend)
    if backend != "thread":
        print(f"(simulated expert ignores --expert-backend {backend}: "
              "table lookups need no process pool)")
    return SimulatedExpert(stream, "gpt-3.5-turbo", workers=workers)


def parse_autoscale(spec: str):
    """Parse ``--autoscale``: '' -> None, 'auto' -> (1, 8), 'LO:HI' ->
    (LO, HI).  The engine scales the expert pool within these bounds off
    queue depth, deterministically at tick boundaries."""
    if not spec:
        return None
    if spec == "auto":
        return (1, 8)
    lo, _, hi = spec.partition(":")
    try:
        return (int(lo), int(hi))
    except ValueError:
        raise SystemExit(
            f"--autoscale expects 'auto' or 'LO:HI', got {spec!r}")


def serve_stream_batched(dataset: str, samples: int, mu: float,
                         batch: int = 64, expert_kind: str = "model",
                         seed: int = 0, log_every: int = 500,
                         mesh=None, updates_per_tick: str = "single",
                         async_delay: int = 0, pipeline_depth: int = 0,
                         expert_workers: int = 1, per_lane: bool = False,
                         ladder: str = "default", trace_out: str = "",
                         arrivals: str = "none", lane_budget: int = 0,
                         admission: str = "queue", queue_limit: int = 0,
                         arrival_rate: float = 1.0, request_len: int = 8,
                         burst_size: int = 8, expert_backend: str = "thread",
                         expert_timeout=None, autoscale=None,
                         checkpoint_every: int = 0,
                         checkpoint_path: str = "", restore: str = ""):
    """Default serving path: the batched multi-stream engine.

    ``mesh`` (a jax Mesh, e.g. from ``launch.mesh.parse_mesh_spec``)
    shards the stream lanes over the mesh's ('pod','data') axes; the
    cascade state stays replicated.  ``updates_per_tick="scaled"``
    lr-scales the per-tick update by the number of expert demos, closing
    the item-space adaptation gap of one-update-per-tick batching.
    ``async_delay >= 1`` overlaps the expert forward with the next ticks'
    student compute (deferred lanes answer provisionally; annotations
    land within that many ticks — core/batched.py ``max_delay``).
    ``pipeline_depth >= 1`` additionally overlaps the route passes
    themselves: up to that many ticks' level-0 forwards stay in flight
    while older ticks' host routing resolves, with results unchanged
    (core/batched.py pipelined route mode).  ``expert_workers >= 2``
    sizes the expert annotation pool (sharded ``submit_many`` tickets),
    and ``per_lane=True`` commits each lane's annotation on the spread
    sub-deadline schedule with per-item updates (core/batched.py
    per-lane commit mode — pair it with the pool).  ``arrivals`` other
    than "none" switches to the continuous-batching front-end
    (core/admission.py): requests arrive on the named seeded schedule
    (data/streams.py), claim lanes from a pool of ``lane_budget``
    (default ``batch``) and retire at their own length, with
    ``admission`` = "queue" (unbounded FCFS wait) or "shed" (drop
    arrivals beyond ``queue_limit`` waiting requests); the report adds
    per-stream time-to-answer percentiles.  ``ladder`` picks the
    level stack: "default" = lr -> tinytf (dense jnp students);
    "kernel" = lr -> tinytf_flash -> ssm with the upper levels' batched
    forwards routed through the Pallas kernels at full default spec
    sizes (TPU-appropriate; interpret-emulated and slow on CPU);
    "kernel-ci" = the same ladder at the CI-sized specs the tier-1
    parity tests pin (docs/MODELS.md).  All of it composes."""
    from repro.data import make_stream
    stream = make_stream(dataset, seed=seed, n_samples=samples)
    expert = _make_expert(stream, stream.spec.n_classes, expert_kind,
                          samples, seed,
                          workers="auto" if autoscale else expert_workers,
                          backend=expert_backend)
    if ladder == "default":
        cfg = default_cascade_config(n_classes=stream.spec.n_classes,
                                     mu=mu, seed=seed,
                                     expert_cost=expert.cost)
    else:
        from repro.core import kernel_cascade_config
        from repro.models.kernel_students import TINY_SSM_CI, TINY_TF_CI
        spec_kw = ({"tf_flash_spec": TINY_TF_CI, "ssm_spec": TINY_SSM_CI}
                   if ladder == "kernel-ci" else {})
        cfg = kernel_cascade_config(n_classes=stream.spec.n_classes,
                                    mu=mu, seed=seed,
                                    expert_cost=expert.cost, **spec_kw)
    lanes_n = lane_budget or batch
    # history_limit=0: the serving loop only reads aggregate metrics, so
    # per-item history would grow without bound on long streams.  The
    # front-end path keeps the per-lane commit log on top of that — its
    # per-stream records need the commit ticks
    engine = BatchedCascadeEngine(cfg, expert, n_streams=lanes_n,
                                  mesh=mesh,
                                  updates_per_tick=updates_per_tick,
                                  max_delay=async_delay,
                                  pipeline_depth=pipeline_depth,
                                  per_lane=per_lane,
                                  history_limit=0,
                                  commit_log=arrivals != "none" or None,
                                  expert_timeout=expert_timeout,
                                  autoscale=autoscale)
    if restore:
        engine.restore_state(restore)
        print(f"restored live state from {restore} (resuming at tick "
              f"{engine.t}, item {engine.t * engine.n_streams})")
    if arrivals != "none":
        return _serve_frontend(
            engine, stream, arrivals, admission=admission,
            queue_limit=queue_limit, arrival_rate=arrival_rate,
            request_len=request_len, burst_size=burst_size, seed=seed,
            trace_out=trace_out)
    t0 = time.time()
    metrics = engine.run(stream, log_every=log_every,
                         checkpoint_every=checkpoint_every,
                         checkpoint_path=checkpoint_path)
    dt = time.time() - t0
    _save_trace(engine, trace_out)
    frac = metrics["expert_calls"] / len(stream)
    lanes = (f"batch={batch}" if mesh is None else
             f"batch={batch} mesh={dict(mesh.shape)}")
    if ladder != "default":
        lanes += f" ladder={ladder}"
    if async_delay:
        lanes += f" async_delay={async_delay}"
    if pipeline_depth:
        st = engine.pipeline_stats
        lanes += (f" pipeline_depth={pipeline_depth} "
                  f"(refetches={st['refetches']} "
                  f"fences={st['update_fences'] + st['budget_fences']})")
    if expert_workers > 1 or per_lane:
        lanes += (f" expert_workers={expert_workers}"
                  f" commit={'lane' if per_lane else 'tick'}")
    cs = engine.commit_stats
    if cs["lanes"]:
        print(f"annotation commits: {cs['lanes']} lanes, "
              f"mean age {cs['age_sum'] / cs['lanes']:.2f} ticks, "
              f"mean latency {cs['wall_sum'] / cs['lanes'] * 1e3:.1f} ms, "
              f"update programs {cs['programs']} "
              f"(private state copies {cs['private_copies']})")
    fs = engine.fault_stats
    if any(fs.values()):
        print(f"fault stats: timeouts={fs['timeouts']} "
              f"worker_deaths={fs['worker_deaths']} "
              f"requeues={fs['requeues']} "
              f"dropped_annotations={fs['dropped_annotations']} "
              f"fleet resizes={len(engine.fleet_log)} "
              f"(final width {engine.expert.workers})")
    print(f"\nserved {len(stream)} queries in {dt:.1f}s "
          f"({metrics['items_per_sec']:.0f} items/s, {lanes})")
    print(f"accuracy={metrics['accuracy']:.4f}  "
          f"expert_calls={metrics['expert_calls']} "
          f"({frac:.1%} of stream)  cost_saving={1-frac:.1%}")
    print(f"level fractions: "
          f"{[round(f, 3) for f in metrics['level_fractions']]}")
    return metrics


def _serve_frontend(engine, stream, arrivals: str, *, admission: str,
                    queue_limit: int, arrival_rate: float,
                    request_len: int, burst_size: int, seed: int,
                    trace_out: str = ""):
    """Continuous-batching serving path: seeded arrival schedule through
    the admission front-end, with a per-stream latency report."""
    from repro.core import CascadeFrontEnd
    from repro.data import arrival_schedule
    if arrivals == "lockstep":
        kw = {"n_lanes": engine.n_streams}
    elif arrivals == "poisson":
        kw = {"rate": arrival_rate, "mean_len": request_len, "seed": seed}
    else:
        kw = {"burst": burst_size, "mean_len": request_len, "seed": seed,
              "every": max(1, int(round(burst_size / arrival_rate)))}
    requests = arrival_schedule(arrivals, len(stream), **kw)
    fe = CascadeFrontEnd(engine, stream, admission=admission,
                         queue_limit=queue_limit)
    t0 = time.time()
    fe.serve(requests)
    dt = time.time() - t0
    _save_trace(engine, trace_out)
    m = fe.metrics()
    served = m["predictions"] >= 0
    acc = (float(np.mean(m["predictions"][served]
                         == stream.labels[served]))
           if served.any() else 0.0)
    cs = engine.commit_stats
    print(f"\nserved {m['items_done']} items of {m['requests']} "
          f"requests in {dt:.1f}s over {m['ticks']} ticks "
          f"(arrivals={arrivals}, lanes={engine.n_streams}, "
          f"admission={admission})")
    print(f"answered={m['answered']} shed={m['shed']}  "
          f"goodput={m['items_done'] / max(dt, 1e-9):.0f} items/s  "
          f"occupancy={m['occupancy_mean']:.2f}/{engine.n_streams} "
          f"(idle ticks={m['idle_ticks']})")
    print(f"time-to-answer p50={m['tta_p50']:.0f} "
          f"p99={m['tta_p99']:.0f} ticks  "
          f"mean queue delay={m['queue_delay_mean']:.2f} ticks")
    if cs["lanes"]:
        print(f"annotation commits: {cs['lanes']} lanes, "
              f"mean age {cs['age_sum'] / cs['lanes']:.2f} ticks")
    print(f"accuracy={acc:.4f} over served items  "
          f"expert_calls={engine.expert_calls_total}")
    m["accuracy"] = acc
    m["records"] = fe.records
    return m


def _save_trace(engine, trace_out: str) -> None:
    """Persist the engine's determinism-sanitizer trace, if both exist.

    Two runs' saved traces (e.g. ``--expert-workers 1`` vs ``4``, or
    ``--pipeline-depth 0`` vs ``2``) feed
    ``repro.analysis.sanitize.diff_traces`` / ``Trace.load`` for a
    first-divergence report at (tick, lane, level, attr) granularity.
    """
    tr = _san.trace_of(engine)
    if not trace_out:
        return
    if tr is None:
        print("--trace-out set but no determinism trace was recorded "
              "(enable with --sanitize determinism)")
        return
    tr.save(trace_out)
    print(f"determinism trace: {len(tr)} tick record(s) -> {trace_out}")


def _sanitizer_reports(modes) -> None:
    """Post-run reports for the enabled runtime sanitizers."""
    if "retrace" in modes:
        rep = _san.retrace_report()
        total = sum(rep.values())
        print(f"retrace sanitizer: {total} compile(s) across "
              f"{len(rep)} step function(s)")
        flagged = _san.retrace_check(limit=16)
        for name, n in sorted(flagged.items()):
            print(f"  UNEXPECTED RETRACES: {name} compiled {n}x — a "
                  "shape/dtype is leaking into the traced signature")
    if "locks" in modes:
        violations = _san.lock_order_violations()
        print(f"lock sanitizer: clean run, "
              f"{len(violations)} order violation(s)")
        for v in violations:
            print(f"  {v}")


def serve_stream(dataset: str, samples: int, mu: float, microbatch: int,
                 expert_kind: str = "model", seed: int = 0,
                 log_every: int = 500, trace_out: str = ""):
    """Sequential reference loop with probe/replay expert micro-batching."""
    from repro.data import make_stream
    stream = make_stream(dataset, seed=seed, n_samples=samples)
    n_classes = stream.spec.n_classes
    expert = _make_expert(stream, n_classes, expert_kind, samples, seed)

    proxy = _BatchProxy(expert)
    cfg = default_cascade_config(n_classes=n_classes, mu=mu, seed=seed,
                                 expert_cost=expert.cost)
    cascade = OnlineCascade(cfg, proxy, history_limit=0)

    preds = np.zeros(len(stream), np.int32)
    t0 = time.time()
    expert_batch_sizes = []
    i = 0
    while i < len(stream):
        j = min(i + microbatch, len(stream))
        batch_idx = list(range(i, j))
        # Pass 1 (probe): predict which queries will reach the expert.
        # Item k of the batch will be processed at tick cascade.t + k + 1;
        # the pre-split tick keys make the probe's jump draws exact.
        need = [k for off, k in enumerate(batch_idx)
                if probe_route(cascade, stream.docs[k],
                               cascade.t + off + 1)]
        # Batched expert forward for just the deferred subset.
        if need:
            lb = getattr(expert, "label_batch", None)
            if lb is not None:
                labels = lb(need, [stream.docs[k] for k in need])
            else:
                labels = [expert.label(k, stream.docs[k]) for k in need]
            for k, y in zip(need, labels):
                proxy.table[k] = int(y)
            expert_batch_sizes.append(len(need))
        # Pass 2 (replay): stream-order Algorithm 1 with online updates.
        for k in batch_idx:
            out = cascade.process(k, stream.docs[k])
            preds[k] = out["prediction"]
        # the replayed micro-batch's precomputed labels are spent — prune
        # them so the proxy table stays O(microbatch), not O(stream)
        for k in batch_idx:
            proxy.table.pop(k, None)
        i = j
        if log_every and i % max(log_every, microbatch) < microbatch:
            acc = float(np.mean(preds[:i] == stream.labels[:i]))
            print(f"[{i}/{len(stream)}] acc={acc:.4f} "
                  f"expert_calls={cascade.expert_calls} "
                  f"({(time.time()-t0)/i*1000:.1f} ms/query)", flush=True)

    _save_trace(cascade, trace_out)
    acc = float(np.mean(preds == stream.labels))
    frac = cascade.expert_calls / len(stream)
    mean_eb = float(np.mean(expert_batch_sizes)) if expert_batch_sizes else 0
    print(f"\nserved {len(stream)} queries in {time.time()-t0:.1f}s")
    print(f"accuracy={acc:.4f}  expert_calls={cascade.expert_calls} "
          f"({frac:.1%} of stream)  cost_saving={1-frac:.1%}")
    print(f"mean expert batch={mean_eb:.1f}  "
          f"probe mispredicts (single-call fallbacks)={proxy.fallback_calls}")
    print(f"level fractions: "
          f"{[round(float(f), 3) for f in (cascade.level_counts / len(stream))]}")
    return {"accuracy": acc, "expert_calls": cascade.expert_calls,
            "mean_expert_batch": mean_eb,
            "fallback_calls": proxy.fallback_calls,
            "predictions": preds}


def main():
    """CLI entry point: parse serving flags and run the chosen engine.

    Engine-composition cheat sheet (all batched-engine knobs compose):
    ``--batch`` sets the lane count, ``--mesh`` shards those lanes over
    devices, ``--async-delay`` takes the expert off the critical path,
    ``--pipeline-depth`` takes the per-tick route sync off it, and
    ``--updates scaled`` keeps item-space adaptation at large batch.
    docs/ARCHITECTURE.md walks the whole tick lifecycle."""
    ap = argparse.ArgumentParser(
        description="Streaming cascade server (online cascade learning)")
    ap.add_argument("--dataset", default="hatespeech",
                    choices=["imdb", "hatespeech", "isear", "fever"],
                    help="which simulated stream corpus to serve "
                         "(data/streams.py; paper's four benchmarks)")
    ap.add_argument("--samples", type=int, default=2000,
                    help="stream length in items (queries served)")
    ap.add_argument("--mu", type=float, default=3e-7,
                    help="cost weighting factor mu (Eq. 1): the user's "
                         "accuracy-vs-LLM-cost budget knob; larger mu "
                         "closes the deferral gates sooner")
    ap.add_argument("--engine", default="batched",
                    choices=["batched", "sequential"],
                    help="'batched' = BatchedCascadeEngine (S lanes in "
                         "lockstep, the serving default); 'sequential' = "
                         "per-item Algorithm-1 reference loop with "
                         "probe/replay expert micro-batching (semantics "
                         "oracle)")
    ap.add_argument("--batch", type=int, default=64,
                    help="concurrent stream lanes S (batched engine): "
                         "each tick serves one item per lane; S=1 is "
                         "bit-identical to the sequential reference")
    ap.add_argument("--mesh", default="",
                    help="lane-shard the batched engine over a device "
                         "mesh, e.g. 'data=8' or 'pod=2,data=4' (set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for virtual CPU devices); cascade "
                         "state stays replicated, --batch must divide "
                         "by the lane-device count")
    ap.add_argument("--updates", default="single",
                    choices=["single", "scaled"],
                    help="per-tick update scheduling (batched engine): "
                         "'scaled' lr-scales the one weighted step by "
                         "the tick's expert-demo count (Optimizer."
                         "step_k), pinning expert-call counts near the "
                         "sequential reference at large --batch")
    ap.add_argument("--async-delay", type=int, default=0,
                    help="bounded annotation delay in ticks (batched "
                         "engine): >=1 overlaps the expert forward with "
                         "student compute — deferred lanes answer "
                         "provisionally and annotations commit exactly "
                         "that many ticks later; 0 = synchronous "
                         "(bit-exact reference semantics)")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="route-pipeline depth P (batched engine): >=1 "
                         "keeps up to P ticks' level-0 forwards in "
                         "flight while older ticks' host routing "
                         "resolves, hiding featurization and transfer "
                         "latency behind device compute; predictions, "
                         "levels and expert calls are identical for any "
                         "P (update ticks fence the pipeline); 0 = "
                         "unpipelined")
    ap.add_argument("--expert-workers", type=int, default=1,
                    help="expert annotation pool size W (batched "
                         "engine): >=2 shards each deferred batch over "
                         "W concurrent annotation workers "
                         "(expert.submit_many) with per-item ticket "
                         "completion; annotations and routing are "
                         "invariant to W — only latency/throughput "
                         "change")
    ap.add_argument("--expert-backend", default="thread",
                    choices=["thread", "process"],
                    help="expert pool backend (batched engine, --expert "
                         "model): 'thread' shares the in-process jit "
                         "cache; 'process' isolates annotation workers "
                         "in spawned processes (ModelExpert ships its "
                         "params to each child once) so a worker crash "
                         "cannot take the engine down — pair with "
                         "--expert-timeout for full fault tolerance. "
                         "'process' is CPU-only: a chip belongs to one "
                         "process, the server already holds it, and "
                         "ModelExpert refuses to spawn children that "
                         "would need it")
    ap.add_argument("--expert-timeout", type=float, default=None,
                    help="per-shard annotation deadline in seconds "
                         "(batched engine): a shard that misses it is "
                         "requeued to another worker (up to max_requeues "
                         "times), then dropped gracefully — the lane "
                         "commits its provisional student answer and "
                         "the drop is counted in fault stats; default = "
                         "wait forever (no requeue path)")
    ap.add_argument("--autoscale", default="",
                    help="elastic expert-fleet bounds 'LO:HI' (or "
                         "'auto' = 1:8): the engine resizes the "
                         "annotation pool within the bounds off pending "
                         "queue depth, decided deterministically at "
                         "tick boundaries (fleet log in fault stats); "
                         "empty = fixed --expert-workers pool")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save live engine state every N ticks to "
                         "--checkpoint-path (classic serving path): "
                         "params, optimizer/deferral state, ring "
                         "buffers, pending annotation queue and fault "
                         "stats — resuming via --restore reproduces the "
                         "uninterrupted run bitwise; 0 = off")
    ap.add_argument("--checkpoint-path", default="",
                    help="checkpoint prefix for --checkpoint-every "
                         "(written atomically; also the --restore "
                         "argument)")
    ap.add_argument("--restore", default="",
                    help="resume serving from a live-state checkpoint "
                         "written by --checkpoint-every; the engine "
                         "picks up at the saved tick and the finished "
                         "run is bitwise the uninterrupted one")
    ap.add_argument("--per-lane-commit", action="store_true",
                    help="per-lane commit granularity (batched engine, "
                         "with --async-delay >= 2): each lane's "
                         "annotation commits on a deterministic "
                         "sub-deadline inside the delay window as a "
                         "per-item update (mean commit age ~(D+1)/2 "
                         "instead of D), in strict (tick, lane) order; "
                         "results are bitwise invariant to worker "
                         "count/latency")
    ap.add_argument("--arrivals", default="none",
                    choices=["none", "lockstep", "poisson", "burst"],
                    help="continuous-batching front-end (batched "
                         "engine, core/admission.py): requests arrive "
                         "on this seeded schedule, claim a lane from "
                         "the pool, run to their own length and retire; "
                         "'none' = classic lockstep batch serving, "
                         "'lockstep' = all requests at t=0 (bitwise the "
                         "classic run), 'poisson'/'burst' = open-loop "
                         "staggered traffic (data/streams.py)")
    ap.add_argument("--lane-budget", type=int, default=0,
                    help="lane-pool capacity for --arrivals serving "
                         "(concurrent streams); 0 = use --batch")
    ap.add_argument("--admission", default="queue",
                    choices=["queue", "shed"],
                    help="overload policy for --arrivals serving: "
                         "'queue' waits arrivals FCFS without bound; "
                         "'shed' drops arrivals beyond --queue-limit "
                         "waiting requests (dropped requests are "
                         "recorded, never served)")
    ap.add_argument("--queue-limit", type=int, default=0,
                    help="waiting-request capacity under --admission "
                         "shed (beyond the free lanes)")
    ap.add_argument("--arrival-rate", type=float, default=1.0,
                    help="offered load for --arrivals poisson/burst, in "
                         "requests per tick")
    ap.add_argument("--request-len", type=int, default=8,
                    help="mean request length in items (geometric) for "
                         "--arrivals poisson/burst")
    ap.add_argument("--burst-size", type=int, default=8,
                    help="requests per burst for --arrivals burst")
    ap.add_argument("--microbatch", type=int, default=16,
                    help="expert micro-batch size (sequential engine): "
                         "the probe/replay pass batches this many "
                         "items' deferred expert calls into one forward")
    ap.add_argument("--expert", default="model",
                    choices=["model", "simulated"],
                    help="'model' trains an in-repo transformer as the "
                         "LLM stand-in (real expert compute); "
                         "'simulated' replays the stream's precomputed "
                         "noisy-teacher annotations (zero compute)")
    ap.add_argument("--ladder", default="default",
                    choices=["default", "kernel", "kernel-ci"],
                    help="level stack (batched engine): 'default' = "
                         "lr -> tinytf dense students; 'kernel' = "
                         "lr -> tinytf_flash -> ssm with the upper "
                         "forwards routed through the Pallas kernels "
                         "(flash/decode attention, SSD scan) at "
                         "full-size specs — TPU-appropriate, interpret-"
                         "emulated on CPU; 'kernel-ci' = the same "
                         "ladder at the CI-sized specs the tier-1 "
                         "parity tests pin (docs/MODELS.md)")
    ap.add_argument("--seed", type=int, default=0,
                    help="stream/cascade RNG seed (core/rng.py per-tick "
                         "key discipline)")
    ap.add_argument("--sanitize", default="",
                    help="comma list of runtime sanitizers to serve "
                         "under (repro.analysis.sanitize): "
                         "'determinism' records the per-tick trace "
                         "(save with --trace-out, diff two runs with "
                         "diff_traces), 'locks' enforces the expert "
                         "pool's # guarded-by: annotations at runtime "
                         "+ lock-order cycles, 'retrace' counts jit "
                         "compiles per step function and flags leaks")
    ap.add_argument("--trace-out", default="",
                    help="write the determinism-sanitizer trace to this "
                         "JSONL path after serving (requires "
                         "--sanitize determinism)")
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind!r} "
          f"x{len(jax.devices())}; Pallas kernels "
          f"{'Mosaic' if dev.platform == 'tpu' else 'interpret mode'}")
    modes = {m.strip() for m in args.sanitize.split(",") if m.strip()}
    if modes:
        _san.enable(modes)    # before engine build: jit probes hook in
    if args.engine == "batched":
        from repro.launch.mesh import parse_mesh_spec
        serve_stream_batched(args.dataset, args.samples, args.mu,
                             batch=args.batch, expert_kind=args.expert,
                             seed=args.seed,
                             mesh=parse_mesh_spec(args.mesh),
                             updates_per_tick=args.updates,
                             async_delay=args.async_delay,
                             pipeline_depth=args.pipeline_depth,
                             expert_workers=args.expert_workers,
                             per_lane=args.per_lane_commit,
                             ladder=args.ladder,
                             trace_out=args.trace_out,
                             arrivals=args.arrivals,
                             lane_budget=args.lane_budget,
                             admission=args.admission,
                             queue_limit=args.queue_limit,
                             arrival_rate=args.arrival_rate,
                             request_len=args.request_len,
                             burst_size=args.burst_size,
                             expert_backend=args.expert_backend,
                             expert_timeout=args.expert_timeout,
                             autoscale=parse_autoscale(args.autoscale),
                             checkpoint_every=args.checkpoint_every,
                             checkpoint_path=args.checkpoint_path,
                             restore=args.restore)
    else:
        serve_stream(args.dataset, args.samples, args.mu, args.microbatch,
                     expert_kind=args.expert, seed=args.seed,
                     trace_out=args.trace_out)
    if modes:
        _sanitizer_reports(modes)


if __name__ == "__main__":
    main()
