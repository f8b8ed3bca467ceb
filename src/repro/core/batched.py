"""Batched multi-stream cascade engine (the serving-scale form of Alg. 1).

``OnlineCascade.process`` is a host-side Python loop: one tiny jitted call
per level per item, plus four more per expert-labeled item for the student
and deferral updates.  At serving scale that is dispatch-bound, not
FLOP-bound.  ``BatchedCascadeEngine`` runs S concurrent stream lanes in
lockstep and replaces the per-item walk with two fused, jitted calls per
tick:

  route pass (read-only, one jitted call per *level*, not per item)
    * the cascade walk is vectorized: per-item control flow becomes
      boolean lane masks (jumped / alive / took) combined with
      ``where``/``argmax`` logic instead of Python ``break``s;
    * each level's predict + defer runs once, batched over the gathered
      subset of lanes still alive at that level — dead lanes (already
      exited, or DAgger-jumped straight to the expert) cost nothing,
      preserving the cascade's compute savings that a naive
      all-levels-times-all-lanes batch would squander.  Subsets are
      padded to bucketed sizes (powers of two up to S) so the number of
      compiled shapes stays bounded;
    * the student models and deferral MLPs are natively batched — this is
      the ``vmap`` of the reference's per-example functions collapsed
      into one dot per level.

  expert call
    * the deferred subset is gathered once and sent to the expert as a
      single batched forward (``label_batch``).

  update pass (per tick, not per item): ONE jitted program per committed
  tick (``sharding.jit_tick_update``), with the ring buffers and every
  level's learned state donated, so they are written in place and the
  host dispatches a single call
    * expert demonstrations are scattered into a vectorized ring buffer
      per level (the FIFO cache of the reference, as one masked scatter);
    * each level's mini-batch is gathered from the written ring at the
      indices the host drew, then one weighted student OGD/Adam step per
      level;
    * one weighted deferral-MLP step per level, with per-item weights
      w[s] = 1[expert labeled s and s reached this level], and skipped
      entirely when no lane has mass — exactly when the reference would
      not step.

    The steps inside the program are the levels' own update methods
    (``_Level.apply_student_update``/``apply_deferral_update``, which the
    reference calls once per item), traced on a copy of each level that
    holds the program's state; they are batched and weighted by design.
    The program donates only state it returned itself: state installed
    from outside (construction, ``reset()``, a restore, a caller's
    weights) is first copied to private buffers (``commit_stats``
    counts the programs and the copies).  At S == 1 the state evolution
    is bit-identical to the reference's separate step programs on the
    CPU (tests/test_batched.py, tests/test_commit_program.py).

RNG / equivalence contract
--------------------------
All randomness follows the pre-split per-tick key discipline of
``repro.core.rng``: lane s at tick t draws from independent child
generators of ``SeedSequence((seed, s, t))``; cache mini-batch sampling
uses the lane-0 children (it is a per-cascade purpose).  The sequential
``OnlineCascade`` is lane 0 of this scheme, and all floating-point update
math lives in functions shared verbatim with the reference
(``*_loss_weighted``, ``deferral_update_terms``), computed in float32 on
device by both engines.  Consequence: **with n_streams == 1 this engine
is bit-for-bit equivalent to ``OnlineCascade`` on the same stream and
seed** — identical predictions, chosen levels, expert calls, parameters,
and optimizer state (tests/test_batched.py asserts this exactly).

Deviations at S > 1 (documented, inherent to batching):
  * students/deferral MLPs take ONE weighted step per tick instead of one
    step per expert-labeled item — k demonstrations within a tick are
    aggregated, which is how batch-serving cascades amortize update cost
    (cf. cascade-aware training; PAPERS.md).  With
    ``updates_per_tick="scaled"`` that single step is lr-scaled to stand
    in for the tick's k per-item steps (``Optimizer.step_k``: EMA decays
    raised to k, schedule counters advanced by k), which pins the
    batched engine's expert-call counts to within ~1.5x of the
    sequential reference on streams where the gates close early
    (tests/test_batched.py pins this);
  * DAgger's beta decays per consumed item (``decay ** S`` per tick, all
    lanes sharing one beta): the students are shared, so the exploration
    budget tracks demonstrations seen, not wall-clock ticks.  The
    re-exploration floor (core.deferral) is applied once per tick at the
    post-tick item count;
  * the hard expert budget is enforced at tick granularity: the first
    ``remaining`` deferred lanes (in lane order) get the expert, the rest
    fall back to the last student's prediction;
  * expert annotations land in the shared ring buffer in lane order
    within the tick.

Async expert queue (``max_delay=``)
-----------------------------------
The tick loop is a route/commit pair around a double-buffered deferred-
lane queue, so the host-side expert forward no longer serializes with
student compute:

  route (tick t)
    * the vectorized cascade walk runs as before; the tick's deferred
      subset is *submitted* to the expert (``expert.submit`` — thread-
      backed for ``ModelExpert``, resolved inline for
      ``SimulatedExpert``) instead of being waited on;
    * deferred lanes emit the LAST student's prediction provisionally
      (its probs are already in hand: every annotated lane calibrates
      every gate, and those calibration forwards run at route time
      against the tick's pre-update students — training-side compute,
      not costed, exactly the values the synchronous engine computes
      after its expert call);
    * expert-call accounting (budget, cost, ``expert_calls``) happens at
      submit time — annotation *latency* never changes which lanes get
      the expert.

  commit (tick t + max_delay, end of tick)
    * the tick's ticket is resolved (blocking if the expert is slower
      than ``max_delay`` ticks of student compute — that is the bound),
      and the annotations are applied exactly as the synchronous engine
      would have: ring-buffer scatter, per-tick weighted student and
      deferral/gate-calibration updates, in FIFO tick order with the
      tick's own cache-sampling RNG.  Commit order is deterministic for
      any expert latency — results never depend on thread timing.

``max_delay=0`` degenerates to the synchronous engine: route submits and
immediately commits inside the same ``process_tick``, executing the
identical op sequence — the S == 1 and lane-sharded parity contracts
hold **bitwise** at ``max_delay=0``.  With ``max_delay=D >= 1`` the
update stream lags the route stream by exactly D ticks (bounded
annotation delay): a tick's route sees parameters that have consumed all
demonstrations up to D+1 ticks back.  Beta still decays per consumed
item per tick at route time, and the demonstrations-seen re-exploration
floor is unchanged — delay shifts *when* updates land, never *which*
draws or annotations occur.  ``flush()`` (called by ``run`` at stream
end and available to servers) drains the queue.  Predictions already
emitted stay provisional — the accuracy cost of the delay is measured,
not hidden (tests/test_async.py pins the bounded-delay regression;
benchmarks/async_throughput.py measures the expert/student overlap win).

Per-lane commit granularity + expert pool (``per_lane=``)
---------------------------------------------------------
The per-tick drain above commits a routed tick's annotations as ONE
block at age exactly D: every deferred lane waits for the whole tick's
ticket, one update aggregates the tick's k demonstrations, and a single
slow annotation batch delays every lane behind it.  ``per_lane=True``
upgrades the queue to per-lane granularity:

  * the deferred subset is submitted through ``expert.submit_many``
    (core/experts.py): the batch is split into ``min(workers, k)``
    contiguous shards annotated by W concurrent workers, and the ticket
    completes *per item* — ``result_slice`` blocks on exactly the
    shards a commit needs, so expert throughput scales with the pool
    instead of serializing behind one worker;
  * each lane commits individually — ring-buffer scatter of its one
    demonstration, a per-item student step sampled with the LANE'S OWN
    tick cache RNG, and a single-item deferral/gate update — i.e. the
    sequential reference's per-item update schedule, recovered inside
    the batched engine (at S == 1 this is bitwise the reference, and
    ``updates_per_tick="scaled"`` becomes a no-op: the per-item steps
    ARE the schedule it approximates);
  * lanes drain on a deterministic sub-deadline schedule (``lanes_due``)
    that spreads a tick's k lanes over the D tick boundaries inside the
    delay window (cumulative ``floor(age * k / D)``, everything due at
    age D) — mean annotation-commit age drops from D to ~(D+1)/2 at
    D >= 2 while the <= D bound is untouched;
  * updates stay in strict (submit-tick, lane) order: the drain only
    advances past a tick's queue head once it is fully committed, and
    blocking on a not-yet-ready shard (never skipping it) is what keeps
    the schedule — and therefore predictions, params, and optimizer
    state — BITWISE IDENTICAL for any worker count and any worker
    latency interleaving.  Worker timing moves wall-clock blocking,
    never semantics (tests/test_pool.py pins W in {1,2,4} and
    adversarial latency schedules).

``per_lane=False`` (default) with ``workers=1`` executes the exact
PR-3 per-tick path.  ``commit_stats`` aggregates per-lane commit age
(ticks) and wall latency (seconds) for both modes;
benchmarks/pool_throughput.py measures the latency and W-scaling wins.

Lane sharding (``mesh=``)
-------------------------
Passing a ``jax.sharding.Mesh`` shards the engine's lane-major arrays —
feature batches, per-lane probs/deferral outputs, called masks, expert
labels, per-item weights — over the mesh's ('pod','data') axes with
``NamedSharding`` (sharding.specs lane rules).  The cascade itself is
ONE shared policy serving S lanes, so students, deferral MLPs, optimizer
state and the demonstration ring buffers live replicated on the mesh;
the per-level gathered predict+defer partitions into N independent
per-device programs (no collectives in the serving path), while the
per-tick weighted update steps and the ring-buffer scatter reduce over
the sharded lane dim through the collectives GSPMD inserts.  The expert
gather stays host-side (the expert is a host object).  ``n_streams``
must divide by the lane-device count; bucketed subset sizes then divide
too (``_bucket`` floors at the device count), except on a partial final
tick, which falls back to replicated placement.  Routing is
host-deterministic, so the sharded engine matches the unsharded engine
on identical tick keys — identical predictions, levels, and expert
calls; parameters agree to float tolerance (SPMD reassociates the
weighted-update reductions).  tests/test_sharded.py asserts this on an
8-virtual-device mesh; benchmarks/sharded_throughput.py measures it.

Pipelined route passes (``pipeline_depth=``)
--------------------------------------------
Even with the expert off the critical path (``max_delay``), the route
pass itself still syncs per level per tick: host routing needs ``dprob``
back from the device before it knows which lanes survive to the next
level, so the host blocks on every tick's first forward while the device
idles through every tick's featurization.  ``pipeline_depth=P >= 1``
overlaps them with a P-deep ring of in-flight ticks:

  dispatch (stage A, ``submit_tick``)
    * tick t+1's jump draws, masks, and level-0 featurization run on the
      host, and its level-0 batched forward (featurize -> ``put_lanes``
      -> jitted predict+defer) is *dispatched* — JAX async dispatch
      returns device futures without blocking — while tick t's dprob
      device->host transfer and host routing are still resolving.
      ``sharding.host_prefetch`` enqueues the D2H copy of the in-flight
      (probs, dprob) pair behind its producing computation, so by the
      time the ring resolves a tick its route outputs are already on the
      host.  Only level 0 can be pre-dispatched: deeper levels' gather
      masks depend on the tick's own earlier dprobs (the cascade's
      sequential structure), but in the converged single-exit regime
      level 0 is the whole tick — exactly where the sync hurt.
  resolve (stage B, FIFO)
    * the oldest in-flight tick blocks on its level-0 handles, walks the
      remaining levels (dispatch+sync per level, as before), submits
      deferred lanes to the expert, and commits due annotations — the
      identical op sequence as the unpipelined engine, in tick order.

Speculation discipline (what makes P > 0 *exact*, not approximate):

  * jump draws, sampled actions, and cache RNG are pre-split per tick
    (core.rng) — dispatch order cannot shift them;
  * beta decay is deterministic in items-seen, so stage A advances a
    route-time beta copy (``_route_beta``) through the identical
    recurrence the resolve-time state follows;
  * **update ticks fence the pipeline**: a dispatched forward reads the
    params live at dispatch.  If a commit is already known to be due
    while the ring drains (the pending queue holds a tick whose D-tick
    delay expires before the newly submitted tick routes), ``submit_tick``
    resolves past it first (``pipeline_stats["update_fences"]``).  A
    commit that only becomes known later — an in-flight tick turns out
    to call the expert at ``max_delay=0`` — is caught at resolve by a
    state-version check and the level-0 forward is *refetched* against
    the committed params (``pipeline_stats["refetches"]``; the
    featurization, which is parameter-independent, is reused).  Hard
    budgets quench speculation only inside the ambiguous window
    (``pipeline_stats["budget_fences"]``): far from the budget edge the
    jump gate's budget bit is provably stable.

Consequence: any ``pipeline_depth`` produces identical predictions,
chosen levels, expert-call decisions, parameters and optimizer state on
identical tick keys — only wall-clock differs (tests/test_pipelined.py
pins this, including composition with ``max_delay`` and the mesh).
``pipeline_depth=0`` (default) keeps today's one-tick-at-a-time
``process_tick`` path bit-for-bit.  In the learning regime every tick
commits, so the pipeline degenerates to the synchronous engine (fence
per tick) — the speedup lives in the converged regime, which is where
serving spends its life (benchmarks/pipelined_throughput.py measures
both honestly).  Pipelined serving is driven through
``submit_tick``/``resolve_tick``/``drain`` (``run`` does); a tick's
results return when it resolves, at most P ticks after submission.

Host spans
----------
Each tick's host phases are ``jax.profiler.TraceAnnotation`` spans named
``ocl.<phase>``, on the profiler's host plane and the device trace's
clock.  They cost about a microsecond each and are recorded only while a
profiler trace runs (``jax.profiler.trace(dir)`` around ``run``).  Every
span carries ``tick`` (the routed tick; a commit also carries ``at``, the
tick committing it) and the counts of its work as arguments:
``ocl.route_dispatch``/``ocl.route_resolve`` (stages A and B),
``ocl.draws`` (per-lane tick RNG), ``ocl.featurize`` (a level's feature
batch), ``ocl.route_pass`` (pad, put and enqueue one level's forward:
real ``rows``, ``bucket``, and for token levels the non-pad ``tokens``
out of ``token_slots``), ``ocl.wait`` (each host block on a device
result), ``ocl.expert`` (annotation submit and label resolve),
``ocl.commit``, ``ocl.sample`` (cache-index draws) and ``ocl.update``
(one update program's dispatch: the per-tick commit's one program as
``step="commit"``, a per-lane commit's programs named as their retrace
probes).
"""
from __future__ import annotations

import copy
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis import sanitize as _san
from repro.core.cascade import (STATE_ATTRS, CascadeConfig, _Level,
                                make_history)
from repro.core.deferral import reexploration_floor
from repro.core.experts import (ExpertShardError, ExpertShardTimeout,
                                ExpertTicket)
from repro.core.rng import (generator_from_state, generator_state,
                            sample_cache_indices, tick_rngs)
from repro.sharding import (host_prefetch, jit_cache_scatter, jit_route_pass,
                            jit_tick_update)

# autoscale unit: target one worker per this many uncommitted deferred
# items (clipped into the configured [lo, hi] fleet bounds)
_AUTOSCALE_ITEMS_PER_WORKER = 4

# checkpoint schema version (save_state/restore_state)
_CKPT_VERSION = 1


def lanes_due(k: int, age: int, max_delay: int, per_lane: bool) -> int:
    """Cumulative count of a routed tick's k annotated lanes whose
    commit deadline has passed ``age`` ticks after routing.

    Per-tick mode: all k at age ``max_delay``, none before.  Per-lane
    mode: the k lanes spread over the D tick boundaries inside the delay
    window — ``floor(age * k / max_delay)`` due by age, everything due
    at ``age >= max_delay`` (the <= D bound).  A pure function of
    (k, age, max_delay, per_lane): the commit schedule never depends on
    worker timing, which is what makes engine results bitwise invariant
    to pool size and annotation latency (tests/test_properties.py pins
    the monotonicity/bound/exactly-once invariants).
    """
    if age >= max_delay:
        return k
    if not per_lane or age <= 0:
        return 0
    return (age * k) // max_delay


def _count_tokens(span: TraceAnnotation, xb: np.ndarray) -> None:
    """Put a token level's fill on its ``ocl.route_pass`` span: the
    non-pad ids of the padded batch (pad is 0 in ``hash_ids``, every real
    id is >= 1) out of its slots.  Feature levels (float rows) get none."""
    if np.issubdtype(xb.dtype, np.integer):
        span.set_metadata(tokens=int(np.count_nonzero(xb)),
                          token_slots=int(xb.size))


def _pack(named) -> Tuple[tuple, tuple]:
    """A tick's named host arrays as one flat buffer per dtype, and the
    static layout ``_unpack`` reads them back by inside the program: one
    host->device put per dtype instead of one per array."""
    groups: dict = {}
    layout = []
    for name, a in named:
        a = np.asarray(a)
        groups.setdefault(a.dtype.str, []).append(a.ravel())
        layout.append((name, a.dtype.str, a.shape))
    return (tuple(np.concatenate(groups[d]) for d in sorted(groups)),
            tuple(layout))


def _unpack(bufs, layout) -> dict:
    """``_pack``'s arrays by name, as static slices of its buffers."""
    dtypes = sorted({d for _, d, _ in layout})
    at = dict.fromkeys(dtypes, 0)
    out = {}
    for name, d, shape in layout:
        n = math.prod(shape)
        out[name] = bufs[dtypes.index(d)][at[d]:at[d] + n].reshape(shape)
        at[d] += n
    return out


def _zero_commit_stats() -> dict:
    """``commit_stats`` of an engine that has committed nothing: lanes,
    their age and wall latency sums, the worst age, the per-tick commits
    run as the one donated update program, and how many of those first
    copied outside state to private buffers."""
    return {"lanes": 0, "age_sum": 0, "age_max": 0, "wall_sum": 0.0,
            "programs": 0, "private_copies": 0}


@dataclass
class _PendingTick:
    """One routed tick whose expert annotations are still in flight.

    Holds exactly what the commit needs to replay the synchronous
    engine's update block once the labels land: the called-lane feature
    rows per level, the route-time probs/dprob of every level at the
    called lanes (gate calibration inputs), and the tick's own
    cache-sampling generators.  ``committed`` is the per-lane drain
    cursor: lanes ``sel_c[:committed]`` have already committed (always 0
    or k in per-tick mode)."""
    ticket: ExpertTicket
    t: int                        # tick this record was routed at
    called: np.ndarray            # (S,) bool — lanes annotated this tick
    sel_c: np.ndarray             # called lane indices
    feats: List[np.ndarray]       # per-level (S, ...) host feature rows
    probs: np.ndarray             # (nlev, S, C) route-time student probs
    dprob: np.ndarray             # (nlev, S) route-time deferral probs
    cache_rngs: list              # per-level np generators (lane-0 tick)
    committed: int = 0            # lanes already committed (prefix)
    lane_cache_rngs: Optional[list] = None   # per called lane, per level
    lanes: Optional[np.ndarray] = None  # physical lane per tick position
                                        # (occupancy ticks; None = arange)
    wall: float = 0.0             # perf_counter at submit (latency stats)
    feats_dev: Optional[list] = None   # device copies of feats, uploaded
                                       # once and shared by the record's
                                       # per-lane scatters
    idxs: Optional[list] = None   # stream indices of the called lanes
                                  # (what a failed shard is requeued as)
    docs_k: Optional[list] = None  # raw docs of the called lanes (None
                                   # after restore: ticket already
                                   # resolved, requeue unreachable)
    requeues: dict = field(default_factory=dict)  # shard lo -> retries


@dataclass
class _InFlightTick:
    """One dispatched-but-unresolved tick of the route pipeline.

    Created by stage A (``_route_dispatch``): the tick's pre-split RNG
    draws, jump mask, level-0 featurization, and the level-0 forward's
    un-synced device handles.  Stage B (``_route_resolve``) turns it into
    the tick's output dict; ``version`` records the engine's commit
    counter at dispatch so a commit landing in between is detected and
    the speculated forward refetched."""

    t: int                        # tick number assigned at dispatch
    indices: List[int]            # per-lane stream indices
    docs: list                    # per-lane raw docs
    S: int                        # lanes in this tick (<= n_streams)
    jump: np.ndarray              # (nlev, S) bool DAgger jump mask
    u_act: np.ndarray             # (nlev, S) float32 sampled-action draws
    budget_ok: bool               # route-time budget gate (fence-stable)
    cache_rngs: list              # per-level cache-sampling generators
    feats_cache: list             # per-level lazily built feature rows
    sel0: np.ndarray              # lanes alive at level 0 (post-jump)
    xb0: Optional[np.ndarray]     # padded level-0 host feature batch
    handles: Optional[tuple]      # in-flight (probs, dprob) device pair
    version: int                  # engine commit counter at dispatch
    beta_after: List[float]       # per-level beta after this tick's decay
    lane_cache: Optional[list] = None   # per-lane cache rngs (per_lane)
    lanes: Optional[np.ndarray] = None  # physical lane per tick position
                                        # (occupancy ticks; None = arange)
    u_jump_raw: Optional[np.ndarray] = None  # (nlev, S) raw jump draws,
                                             # kept only under the
                                             # determinism sanitizer


class BatchedCascadeEngine:
    """Lockstep multi-stream driver for Algorithm 1.

    ``process_tick(indices, docs)`` advances every lane by one item; lane
    s of tick t handles ``docs[s]`` (its expert annotation is requested as
    ``expert.label(indices[s], docs[s])`` or the batched equivalent).
    """

    def __init__(self, config: CascadeConfig, expert, n_streams: int = 64,
                 *, updates_per_tick: str = "single", mesh=None,
                 max_delay: int = 0, pipeline_depth: int = 0,
                 per_lane: bool = False,
                 history_limit: Optional[int] = None,
                 commit_log: Optional[bool] = None,
                 expert_timeout: Optional[float] = None,
                 max_requeues: int = 2,
                 autoscale: Optional[Tuple[int, int]] = None,
                 readiness_commits: bool = False):
        if n_streams < 1:
            raise ValueError("n_streams must be >= 1")
        if updates_per_tick not in ("single", "scaled"):
            raise ValueError(
                f"updates_per_tick must be 'single' or 'scaled', "
                f"got {updates_per_tick!r}")
        if max_delay < 0:
            raise ValueError(f"max_delay must be >= 0, got {max_delay}")
        if pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth}")
        if expert_timeout is not None and expert_timeout <= 0:
            raise ValueError(
                f"expert_timeout must be > 0 (or None), got {expert_timeout}")
        if max_requeues < 0:
            raise ValueError(f"max_requeues must be >= 0, got {max_requeues}")
        # an expert constructed with workers="auto" opts into autoscaling
        # even when the engine caller didn't pass bounds
        if autoscale is None and getattr(expert, "auto_workers", False):
            autoscale = (1, 8)
        if autoscale is True:
            autoscale = (1, 8)
        if autoscale is not None:
            lo, hi = int(autoscale[0]), int(autoscale[1])
            if not (1 <= lo <= hi):
                raise ValueError(
                    f"autoscale bounds must satisfy 1 <= lo <= hi, "
                    f"got ({lo}, {hi})")
            autoscale = (lo, hi)
            if not hasattr(expert, "workers"):
                raise ValueError(
                    "autoscale requires an expert with a mutable "
                    "`workers` fleet width")
        self.cfg = config
        self.expert = expert
        self.n_streams = n_streams
        self.updates_per_tick = updates_per_tick
        self.max_delay = int(max_delay)
        self.pipeline_depth = int(pipeline_depth)
        self.per_lane = bool(per_lane)
        self.expert_timeout = expert_timeout
        self.max_requeues = int(max_requeues)
        self.autoscale = autoscale
        self.readiness_commits = bool(readiness_commits)
        if autoscale is not None:
            expert.workers = autoscale[0]
            # pools sized once take the upper bound so scaling up never
            # needs an executor rebuild (ModelExpert._pool_width)
            if getattr(expert, "max_workers", False) is None:
                expert.max_workers = autoscale[1]
        self.mesh = mesh
        if mesh is not None:
            from repro.sharding import (lane_count, put_lanes,
                                        put_replicated,
                                        replicated_sharding)
            self._rep_sharding = replicated_sharding(mesh)
            n_lane = lane_count(mesh)
            if n_lane < 1 or n_streams % n_lane:
                raise ValueError(
                    f"n_streams={n_streams} must be a positive multiple "
                    f"of the mesh's lane-device count {n_lane}")
            self._n_lane_devices = n_lane
            self._put_lane = lambda x: put_lanes(x, mesh)
            self._put_rep = lambda x: put_replicated(x, mesh)
        else:
            self._n_lane_devices = 1
            self._put_lane = jnp.asarray
            self._put_rep = jnp.asarray
        keys = jax.random.split(jax.random.PRNGKey(config.seed),
                                len(config.levels))
        # identical construction (and PRNG keys) to OnlineCascade so the
        # initial parameters match the reference bitwise
        self.levels: List[_Level] = [
            _Level(spec, config, k,
                   defer_cost=(config.levels[i + 1].cost
                               if i + 1 < len(config.levels)
                               else config.expert_cost))
            for i, (spec, k) in enumerate(zip(config.levels, keys))]
        nlev = len(self.levels)
        if mesh is not None:
            # the cascade is SHARED across lanes: students, deferral MLPs
            # and their optimizer states live replicated on the mesh (and
            # the levels' reset() snapshots point at the replicated
            # copies, so a reset engine stays mesh-placed)
            for lvl in self.levels:
                (lvl.params, lvl.opt_state, lvl.dparams,
                 lvl.dopt_state) = jax.device_put(
                    (lvl.params, lvl.opt_state, lvl.dparams,
                     lvl.dopt_state), self._rep_sharding)
                lvl._init_state = (lvl.params, lvl.opt_state,
                                   lvl.dparams, lvl.dopt_state)
        # vectorized ring buffers (device) + host mirrors of fill/ptr
        self._cache_x = [self._put_rep(lvl.cache_x) for lvl in self.levels]
        self._cache_y = [self._put_rep(lvl.cache_y) for lvl in self.levels]
        self._cache_n = [0] * nlev
        self._cache_ptr = [0] * nlev
        # the levels' learned state as the update program last returned
        # it: the only state it may donate (``_own_state``)
        self._owned: Optional[tuple] = None
        self.t = 0
        # per-stream accounting (independent per lane)
        S = n_streams
        self.expert_calls = np.zeros(S, np.int64)
        self.total_cost = np.zeros(S, np.float64)
        self.level_counts = np.zeros((S, nlev + 1), np.int64)
        self.items_seen = np.zeros(S, np.int64)
        self.J_cum = np.zeros(S, np.float64)
        self.history = make_history(history_limit)
        # double-buffered deferred-lane queue: routed ticks whose expert
        # annotations are still in flight (at most max_delay + 1 deep)
        self._pending: deque = deque()
        # per-lane annotation-commit accounting: ages in ticks, latencies
        # in seconds, aggregated over every committed lane (both commit
        # modes).  commit_log records (submit_tick, lane, commit_tick)
        # per lane.  By default (commit_log=None) it follows the history
        # mode: on in the unbounded-diagnostics mode (history_limit=None),
        # off with bounded/disabled history so long-serving memory stays
        # bounded (the queue-drain invariant tests and pool_throughput
        # read it).  commit_log=True/False overrides that coupling — the
        # admission front-end needs per-lane commit ticks for its
        # per-stream records while running with history_limit=0
        # (core/admission.py consumes the log with a cursor).
        self.commit_stats = _zero_commit_stats()
        if commit_log is None:
            commit_log = history_limit is None
        self.commit_log: Optional[list] = [] if commit_log else None
        # route pipeline: dispatched-but-unresolved ticks (<= pipeline_depth
        # deep), the speculative route-time beta/item counters that track
        # the resolve-time state through the identical recurrence, and the
        # commit counter the staleness check reads
        self._ring: deque = deque()
        self._route_beta: List[float] = [config.beta0] * nlev
        self._route_items = 0
        self._state_version = 0
        self.pipeline_stats = {"submitted": 0, "resolved": 0,
                               "refetches": 0, "update_fences": 0,
                               "budget_fences": 0}
        # failure-semantics + fleet accounting (ARCHITECTURE.md §10):
        # every injected/observed fault is either healed (requeues) or
        # explicitly surrendered (dropped_annotations) — never silent
        self.fault_stats = {"timeouts": 0, "worker_deaths": 0,
                            "requeues": 0, "dropped_annotations": 0,
                            "scale_ups": 0, "scale_downs": 0}
        self.fleet_log: List[Tuple[int, int]] = []   # (tick, new width)
        self._build_steps()

    def reset(self):
        """Back to tick 0 of a fresh stream; compiled jits are kept (a
        warmed engine can serve new streams with zero compile cost)."""
        for lvl in self.levels:
            lvl.reset()
        nlev = len(self.levels)
        # device ring buffers may have been donated — rebuild from the
        # levels' (zeroed) host templates, on the same mesh placement
        self._cache_x = [self._put_rep(lvl.cache_x) for lvl in self.levels]
        self._cache_y = [self._put_rep(lvl.cache_y) for lvl in self.levels]
        self._cache_n = [0] * nlev
        self._cache_ptr = [0] * nlev
        self._owned = None
        self.t = 0
        self.expert_calls[:] = 0
        self.total_cost[:] = 0
        self.level_counts[:] = 0
        self.items_seen[:] = 0
        self.J_cum[:] = 0
        if self.history is not None:
            for v in self.history.values():
                v.clear()
        # in-flight annotations and route dispatches belong to the
        # abandoned stream
        self._pending.clear()
        self._ring.clear()
        self._route_beta = [self.cfg.beta0] * len(self.levels)
        self._route_items = 0
        self._state_version += 1
        for k in self.pipeline_stats:
            self.pipeline_stats[k] = 0
        self.commit_stats = _zero_commit_stats()
        if self.commit_log is not None:
            self.commit_log.clear()
        for k in self.fault_stats:
            self.fault_stats[k] = 0
        self.fleet_log.clear()
        if self.autoscale is not None:
            self.expert.workers = self.autoscale[0]
        # reap the expert's worker pool: a reset engine must not leak
        # the old stream's threads/processes (pools rebuild lazily on
        # the next submit, so a warmed engine loses no semantics)
        self.close()
        # a recorded determinism-sanitizer trace belongs to the old
        # stream too — a reused engine starts a fresh, comparable trace
        _san.drop_trace(self)

    def close(self) -> None:
        """Shut down the expert's worker pool, if it has one
        (idempotent; the pool is rebuilt lazily on the next submit)."""
        close = getattr(self.expert, "close", None)
        if close is not None:
            close()

    def __del__(self):  # best-effort: don't leak expert workers at GC
        try:
            self.close()
        except Exception:
            pass

    # -- aggregates -----------------------------------------------------
    @property
    def expert_calls_total(self) -> int:
        """Expert calls summed over lanes (resolved ticks only)."""
        return int(self.expert_calls.sum())

    def _budget_exhausted(self) -> bool:
        hb = self.cfg.hard_budget
        return hb is not None and self.expert_calls_total >= hb

    # -- jitted steps ----------------------------------------------------
    def _build_steps(self):
        levels = self.levels
        nlev = len(levels)
        bs_list = [min(lvl.spec.batch_size, lvl.spec.cache_size)
                   for lvl in levels]

        # per-level batched predict + defer over the gathered alive
        # subset (the level's ``route_pass`` body — at a (1, ...) batch
        # this is the reference's ``predict_and_defer`` computation
        # exactly).  In pipelined mode on a mesh the padded lane feature
        # buffer is donated: each in-flight tick's input is consumed
        # exactly once by its dispatch (sharding.jit_route_pass)
        donate_mesh = self.mesh if self.pipeline_depth else None
        self._predict_defer = [
            jit_route_pass(
                _san.trace_probe(f"route_pass[{i}]", lvl.route_pass),
                donate_mesh)
            for i, lvl in enumerate(levels)]

        def scatter(cx_t, cy_t, feats_t, y_full, called, ptr_arr):
            """Vectorized ring-buffer insert of a tick's demonstrations."""
            order = jnp.cumsum(called.astype(jnp.int32)) - 1
            k = jnp.sum(called.astype(jnp.int32))
            new_cx, new_cy = [], []
            for i in range(nlev):
                size = levels[i].spec.cache_size
                # called lanes take consecutive slots after ptr; if
                # k > size only the last `size` survive (the sequential
                # FIFO's overwrite order); index `size` drops the write
                keep = called & (order >= k - size)
                slot = jnp.where(keep, (ptr_arr[i] + order) % size, size)
                new_cx.append(cx_t[i].at[slot].set(feats_t[i], mode="drop"))
                new_cy.append(cy_t[i].at[slot].set(y_full, mode="drop"))
            return tuple(new_cx), tuple(new_cy)

        # ring buffers donated; with a mesh the outputs stay pinned
        # replicated so the donation chain survives the per-lane commit
        # mode's one-scatter-per-lane cadence (sharding.jit_cache_scatter)
        self._scatter = jit_cache_scatter(
            _san.trace_probe("cache_scatter", scatter), self.mesh)

        def student_step(cx_t, cy_t, state, bufs, layout):
            """A committed tick's whole update pass: the ring scatter,
            each level's mini-batch gathered from the written ring at the
            host-drawn indices, then each level's student and deferral-
            gate steps.  The steps are the levels' own update methods,
            traced on a copy of the level that holds this program's
            state.  (Its XLA program is ``jit_student_step``, the name
            under which the update pass's device time is read.)"""
            a = _unpack(bufs, layout)
            new_cx, new_cy = scatter(
                cx_t, cy_t, tuple(a[f"feats{i}"] for i in range(nlev)),
                a["y_full"], a["called"] != 0, a["ptr"])
            k = a.get("k")
            new_state = []
            for i, lvl in enumerate(levels):
                view = copy.copy(lvl)
                for attr, v in zip(STATE_ATTRS, state[i]):
                    setattr(view, attr, v)
                idx = a[f"idx{i}"]
                view.apply_student_update(new_cx[i][idx], new_cy[i][idx],
                                          a[f"w{i}"], k)
                view.apply_deferral_update(a[f"probs{i}"], a["y"],
                                           a[f"reach{i}"], a["dw"], k)
                new_state.append(tuple(getattr(view, attr)
                                       for attr in STATE_ATTRS))
            return new_cx, new_cy, tuple(new_state)

        # ring buffers and learned state donated: one dispatch per
        # committed tick, updated in place (sharding.jit_tick_update)
        self._update = jit_tick_update(
            _san.trace_probe("commit", student_step), self.mesh)
        self._bs_list = bs_list

    def _bucket(self, n: int) -> int:
        """Smallest padded batch size for a subset of n lanes: the
        lane-device count doubled up to at least max(8, n), capped at
        n_streams — every bucket stays divisible by the device count
        (including non-power-of-two meshes) and each level compiles
        O(log S) shapes.  Without a mesh this reduces to the powers-of-
        two-from-8 schedule, and with n_streams == 1 it is exactly 1 —
        the reference's per-item shape, which keeps the parity contract
        bitwise."""
        b = self._n_lane_devices
        while b < max(8, n):
            b *= 2
        return min(b, self.n_streams)

    # -- expert ---------------------------------------------------------
    def _expert_label_batch(self, idxs: Sequence[int], docs) -> np.ndarray:
        lb = getattr(self.expert, "label_batch", None)
        if lb is not None:
            return np.asarray(lb(idxs, docs), np.int32)
        return np.asarray([self.expert.label(i, d)
                           for i, d in zip(idxs, docs)], np.int32)

    def _expert_submit(self, idxs: Sequence[int], docs) -> ExpertTicket:
        """Enqueue a batch annotation.  Experts with a worker pool
        (``submit_many``) get the batch sharded with per-item ticket
        completion — what the per-lane commit drain consumes; experts
        with only ``submit`` keep the PR-3 single-request path, and
        experts without the async interface resolve synchronously
        (still one batched call)."""
        sub = getattr(self.expert, "submit_many", None)
        if sub is None:
            sub = getattr(self.expert, "submit", None)
        if sub is not None:
            return sub(idxs, docs)
        return ExpertTicket(labels=self._expert_label_batch(idxs, docs))

    def _expert_poll(self, ticket: ExpertTicket) -> np.ndarray:
        poll = getattr(self.expert, "poll", None)
        if poll is not None:
            return np.asarray(poll(ticket, block=True), np.int32)
        return np.asarray(ticket.result(), np.int32)

    # -- failure semantics: requeue deadline + graceful degradation ------
    def _resolve_labels(self, rec: _PendingTick, lo: int,
                        hi: int) -> np.ndarray:
        """Labels for called items ``[lo, hi)`` of a pending record,
        surviving shard failures.

        ``expert_timeout`` bounds the wait on each shard (the D-tick
        commit bound becomes a *deadline*, not an assumption about the
        expert).  A timed-out or dead-worker shard is requeued to
        another worker; after ``max_requeues`` retries it is
        force-resolved to the ``-1`` dropped-annotation sentinel
        (counted in ``fault_stats["dropped_annotations"]``), so this
        ALWAYS returns and commits never deadlock.  Annotation labels
        are deterministic functions of the items (both expert kinds),
        so a successful requeue yields the exact labels the original
        shard would have — fault timing never changes committed state,
        only permanent drops do."""
        with TraceAnnotation("ocl.expert", tick=rec.t, rows=hi - lo):
            while True:
                try:
                    return np.asarray(rec.ticket.result_slice(
                        lo, hi, timeout=self.expert_timeout), np.int32)
                except ExpertShardError as e:
                    self._requeue_shard(rec, e)

    def _requeue_shard(self, rec: _PendingTick, err: ExpertShardError):
        k = rec.sel_c.size
        lo = err.lo
        hi = k if err.hi is None else err.hi
        if isinstance(err, ExpertShardTimeout):
            self.fault_stats["timeouts"] += 1
        else:
            self.fault_stats["worker_deaths"] += 1
        tries = rec.requeues.get(lo, 0)
        sub = getattr(self.expert, "submit", None)
        if tries < self.max_requeues and sub is not None \
                and rec.docs_k is not None:
            rec.requeues[lo] = tries + 1
            self.fault_stats["requeues"] += 1
            # resubmit just the failed range as one fresh shard (a new
            # submit sequence — a fresh worker, or for FlakyExpert a
            # fresh scripted fault cell); not re-counted in expert_calls:
            # the annotation was already requested and costed at route
            rec.ticket.replace(lo, hi, sub(rec.idxs[lo:hi],
                                           rec.docs_k[lo:hi]))
        else:
            # graceful degradation: the provisional student answer
            # stands; the lost demonstration is counted, never silent
            rec.ticket.force_resolve(lo, hi,
                                     np.full(hi - lo, -1, np.int32))
            self.fault_stats["dropped_annotations"] += hi - lo

    # -- fleet autoscaling ----------------------------------------------
    def _autoscale_tick(self) -> None:
        """Queue-depth worker autoscaling, decided at the deterministic
        tick boundary (dispatch time): the uncommitted deferred-item
        count is a pure function of the commit schedule under the
        default deterministic drain, so two runs of the same stream make
        identical scale decisions regardless of worker timing — traces
        stay comparable (``fleet_log`` records every decision).  Width
        only changes future shard layouts, never labels, so autoscaling
        preserves the bitwise-invariance contract."""
        lo, hi = self.autoscale
        depth = sum(r.sel_c.size - r.committed for r in self._pending)
        target = min(hi, max(lo, -(-depth // _AUTOSCALE_ITEMS_PER_WORKER)))
        cur = int(self.expert.workers)
        if target != cur:
            key = "scale_ups" if target > cur else "scale_downs"
            self.fault_stats[key] += 1
            self.expert.workers = target
            self.fleet_log.append((self.t, int(target)))

    # -- one lockstep tick ----------------------------------------------
    def process_tick(self, indices: Sequence[int], docs, *,
                     lanes=None, stream_ids=None,
                     stream_ticks=None) -> dict:
        """Advance every lane by one item.  len(docs) may be < n_streams
        on the final partial tick of a stream.

        This is the depth-0 path: dispatch and resolve run back to back,
        so the returned dict is always this tick's own result — bitwise
        the pre-pipeline engine regardless of ``pipeline_depth``.
        Pipelined serving (results returned up to P ticks late, route
        passes overlapped) is driven through ``submit_tick``/
        ``resolve_tick``/``drain`` instead; mixing the two while ticks
        are in flight is an error.

        ``lanes``/``stream_ids``/``stream_ticks`` are the occupancy
        extension used by the continuous-batching front-end
        (core/admission.py): ``lanes`` names the physical lane each tick
        position occupies (strictly increasing, defaults to
        ``arange(S)`` — the lockstep identity), and
        ``stream_ids[s]``/``stream_ticks[s]`` replace ``(s, t)`` as the
        position's RNG tick key so a dynamically-admitted stream draws
        the exact per-item randomness it would have drawn in a dedicated
        lane (see core/rng.py).  All three default to the lockstep
        behaviour bitwise.  An EMPTY tick (S == 0) is legal and advances
        the tick clock — including the D-tick commit deadlines — without
        dispatching any forward; the front-end uses it for idle ticks so
        one clock covers busy and idle time."""
        if self._ring:
            raise RuntimeError(
                "route pipeline has in-flight ticks: resolve_tick()/"
                "drain() them first, or drive the engine entirely "
                "through submit_tick()")
        return self._route_resolve(self._route_dispatch(
            indices, docs, lanes=lanes, stream_ids=stream_ids,
            stream_ticks=stream_ticks))

    # -- pipelined route driver (stage A / stage B) ----------------------
    def submit_tick(self, indices: Sequence[int], docs, *,
                    lanes=None, stream_ids=None,
                    stream_ticks=None) -> List[dict]:
        """Dispatch one tick into the route pipeline (stage A).

        Returns the output dicts of every tick the call resolved, oldest
        first: ring overflow past ``pipeline_depth``, plus any ticks
        resolved early by a fence (a due commit, or a hard budget inside
        its ambiguous window — see the module docstring).  With
        ``pipeline_depth=0`` the submitted tick itself resolves
        immediately, so exactly one dict comes back."""
        outs: List[dict] = []
        S = len(docs)
        hb = self.cfg.hard_budget
        if hb is not None and self._ring:
            resolved_calls = self.expert_calls_total
            in_flight = sum(r.S for r in self._ring)
            if resolved_calls < hb and resolved_calls + in_flight + S > hb:
                # ambiguous budget window: the new tick's jump gate can
                # no longer be proven stable against in-flight expert
                # calls — drain so it reads the exact call count
                self.pipeline_stats["budget_fences"] += 1
                while self._ring:
                    outs.append(self._route_resolve(self._ring.popleft()))
        while self._ring and self._commit_due():
            # a commit is due while the ring drains: dispatching now is
            # guaranteed stale — resolve past the commit first
            self.pipeline_stats["update_fences"] += 1
            outs.append(self._route_resolve(self._ring.popleft()))
        self._ring.append(self._route_dispatch(
            indices, docs, lanes=lanes, stream_ids=stream_ids,
            stream_ticks=stream_ticks))
        while len(self._ring) > self.pipeline_depth:
            outs.append(self._route_resolve(self._ring.popleft()))
        return outs

    def _commit_due(self) -> bool:
        """True when the pending queue's head has lanes whose deadline
        falls at/before the end of the current tick — i.e. a dispatch
        issued now is guaranteed to read pre-commit params.  Per-tick
        mode reduces to the PR-3 condition (head tick's age reached
        max_delay); per-lane mode also fires on the intermediate
        sub-deadlines of the spread schedule (``lanes_due``)."""
        if not self._pending:
            return False
        rec = self._pending[0]
        return lanes_due(rec.sel_c.size, self.t - rec.t, self.max_delay,
                         self.per_lane) > rec.committed

    def resolve_tick(self) -> Optional[dict]:
        """Resolve the oldest in-flight tick (stage B); None if empty."""
        if not self._ring:
            return None
        return self._route_resolve(self._ring.popleft())

    def drain(self) -> List[dict]:
        """Resolve every in-flight tick, oldest first (stream end /
        before checkpointing; ``run`` calls it before ``flush``)."""
        outs = []
        while self._ring:
            outs.append(self._route_resolve(self._ring.popleft()))
        return outs

    def _dispatch_level(self, i: int, fi: np.ndarray, sel: np.ndarray,
                        t: int, calib: int = 0):
        """Pad the gathered lane subset ``fi[sel]`` to its bucket and
        dispatch the level-i route pass (async — no host sync).

        Returns ``(handles, xb)``: the in-flight (probs, dprob) device
        pair and the padded host batch (kept by stage A for refetch).
        Shared by the stage-A dispatch, the stage-B walk, and the
        every-gate calibration forwards (``calib=1``) so the
        pad/bucket/placement rule cannot drift between them."""
        lvl = self.levels[i]
        B = self._bucket(sel.size)
        with TraceAnnotation("ocl.route_pass", tick=t, level=i,
                             rows=sel.size, bucket=B,
                             calib=calib) as span:
            xb = np.zeros((B,) + fi.shape[1:], fi.dtype)
            xb[:sel.size] = fi[sel]
            _count_tokens(span, xb)
            handles = self._predict_defer[i](lvl.params, lvl.dparams,
                                             self._put_lane(xb))
        return handles, xb

    def _route_dispatch(self, indices: Sequence[int], docs, *,
                        lanes=None, stream_ids=None,
                        stream_ticks=None) -> _InFlightTick:
        """Stage A: draws, masks, level-0 featurize + async dispatch.

        Everything here is either deterministic in the tick number
        (pre-split RNG, the route-time beta recurrence) or covered by a
        fence/staleness check (budget bit, level-0 params) — see the
        module docstring's speculation discipline.  The occupancy
        arguments (``lanes``/``stream_ids``/``stream_ticks``, see
        ``process_tick``) only change which physical lane each position
        accounts to and which (stream, tick) key seeds its draws — the
        route itself is position-indexed and identical."""
        cfg = self.cfg
        nlev = len(self.levels)
        S = len(docs)
        if S > self.n_streams:
            raise ValueError(f"tick of {S} items > n_streams={self.n_streams}")
        if lanes is not None:
            lanes = np.asarray(lanes, np.int64)
            if lanes.shape != (S,):
                raise ValueError(
                    f"lanes must have one entry per tick position: "
                    f"got shape {lanes.shape} for a tick of {S}")
            if S and (lanes[0] < 0 or lanes[-1] >= self.n_streams
                      or np.any(np.diff(lanes) <= 0)):
                raise ValueError(
                    "lanes must be strictly increasing physical lane ids "
                    f"in [0, n_streams={self.n_streams})")
        if stream_ids is not None and len(stream_ids) != S:
            raise ValueError("stream_ids must have one entry per position")
        if stream_ticks is not None and len(stream_ticks) != S:
            raise ValueError("stream_ticks must have one entry per position")
        self.t += 1
        t = self.t
        with TraceAnnotation("ocl.route_dispatch", tick=t, lanes=S):
            self.pipeline_stats["submitted"] += 1
            if self.autoscale is not None:
                self._autoscale_tick()

            # lazy per-level featurization: a level's feature batch is only
            # built if some lane actually reaches it (mirrors the reference's
            # per-item feat() cache; in a cheap-level-dominant steady state
            # the expensive levels' featurizers never run)
            feats_cache: list = [None] * nlev

            u_jump = np.empty((nlev, S))
            u_act = np.empty((nlev, S), np.float32)
            cache_rngs = None
            # per-lane commit mode samples each lane's cache mini-batch with
            # the LANE'S OWN tick generators (the sequential reference's
            # per-item rule); per-tick mode only needs the lane-0 purpose
            lane_cache = [] if self.per_lane else None
            with TraceAnnotation("ocl.draws", tick=t, lanes=S):
                for s in range(S):
                    # a dynamically-admitted stream keeps its OWN (stream
                    # id, local tick) key regardless of which lane or
                    # global tick serves it — this is what makes its
                    # per-item draws identical to the dedicated-lane run
                    # (tests/test_admission.py pins it)
                    sid = s if stream_ids is None else int(stream_ids[s])
                    lt = t if stream_ticks is None else int(stream_ticks[s])
                    r = tick_rngs(cfg.seed, sid, lt, nlev)
                    u_jump[:, s] = r.jump.random(nlev)
                    u_act[:, s] = r.action.random(nlev).astype(np.float32)
                    if lane_cache is not None:
                        lane_cache.append(r.cache)
                    if s == 0:
                        cache_rngs = r.cache

            budget_ok = not self._budget_exhausted()
            betas = np.array(self._route_beta)[:, None]
            jump = (u_jump < betas) & budget_ok

            # level 0 is the only forward whose gather mask is known before
            # any dprob returns (lanes alive there = lanes that didn't jump);
            # dispatch it without blocking and start the D2H copy of its
            # outputs so stage B's np.asarray is a wait, not a round trip
            sel0 = np.flatnonzero(~jump[0])
            xb0 = None
            handles = None
            if sel0.size:
                with TraceAnnotation("ocl.featurize", tick=t, level=0,
                                     rows=S):
                    fi = np.stack([self.levels[0].featurize(d)
                                   for d in docs])
                feats_cache[0] = fi
                handles, xb0 = self._dispatch_level(0, fi, sel0, t)
                host_prefetch(handles)

            # beta decays per consumed ITEM (decay^S per tick): the students
            # are shared across lanes, so the DAgger exploration budget is
            # measured in demonstrations seen, matching the reference's
            # schedule in item-space (identical at S == 1).  The
            # re-exploration floor (core.deferral) is applied once per tick
            # at the post-tick item count.  The recurrence is deterministic
            # in items seen, so it advances HERE, at dispatch (tick sizes
            # are known) — ``lvl.beta`` is synced to the same value when the
            # tick resolves, keeping the observable state identical to the
            # unpipelined engine without a second copy of the schedule.
            self._route_items += S
            for i, lvl in enumerate(self.levels):
                self._route_beta[i] = max(
                    self._route_beta[i] * lvl.spec.beta_decay ** S,
                    reexploration_floor(lvl.spec.beta_floor,
                                        self._route_items))

            return _InFlightTick(
                t=t, indices=[int(i) for i in indices], docs=list(docs), S=S,
                jump=jump, u_act=u_act, budget_ok=budget_ok,
                cache_rngs=cache_rngs, feats_cache=feats_cache, sel0=sel0,
                xb0=xb0, handles=handles, version=self._state_version,
                beta_after=list(self._route_beta), lane_cache=lane_cache,
                lanes=lanes,
                u_jump_raw=u_jump if _san.determinism_on() else None)

    def _route_resolve(self, rec: _InFlightTick) -> dict:
        """Stage B: host routing, expert submit, commits, accounting.

        Runs the unpipelined engine's op sequence for tick ``rec.t``
        exactly, in FIFO tick order; the only pipelined difference is
        that the level-0 forward was dispatched earlier (and is refetched
        here if a commit landed since)."""
        with TraceAnnotation("ocl.route_resolve", tick=rec.t,
                             lanes=rec.S) as span:
            cfg = self.cfg
            nlev = len(self.levels)
            S = rec.S
            t = rec.t
            docs = rec.docs
            u_act = rec.u_act
            jump = rec.jump
            budget_ok = rec.budget_ok
            cache_rngs = rec.cache_rngs
            feats_cache = rec.feats_cache
            self.pipeline_stats["resolved"] += 1

            def feats(i):
                if feats_cache[i] is None:
                    with TraceAnnotation("ocl.featurize", tick=t, level=i,
                                         rows=S):
                        feats_cache[i] = np.stack(
                            [self.levels[i].featurize(d) for d in docs])
                return feats_cache[i]

            handles = rec.handles
            if handles is not None and rec.version != self._state_version:
                # a commit landed after this tick's dispatch: the
                # speculated level-0 forward read pre-update params.
                # Refetch against the committed state (featurization is
                # parameter-independent and is reused; only the jitted
                # forward re-runs)
                self.pipeline_stats["refetches"] += 1
                lvl = self.levels[0]
                with TraceAnnotation("ocl.route_pass", tick=t, level=0,
                                     rows=rec.sel0.size,
                                     bucket=rec.xb0.shape[0],
                                     calib=0) as pass_span:
                    _count_tokens(pass_span, rec.xb0)
                    handles = self._predict_defer[0](
                        lvl.params, lvl.dparams, self._put_lane(rec.xb0))

            # -- vectorized cascade walk: one gathered, batched
            #    predict+defer call per level over the lanes still alive
            #    there -------------------------------------------------------
            alive = np.ones(S, bool)            # walking, not yet exited
            jumped = np.zeros(S, bool)
            eval_mask = np.zeros((nlev, S), bool)
            dprob_h = np.zeros((nlev, S), np.float32)
            probs_h = np.zeros((nlev, S, cfg.n_classes), np.float32)
            predictions = np.zeros(S, np.int64)
            exit_level = np.full(S, nlev, np.int64)   # nlev = reached expert
            for i, lvl in enumerate(self.levels):
                jump_now = alive & jump[i]
                jumped |= jump_now
                alive &= ~jump[i]
                sel = np.flatnonzero(alive)
                if sel.size == 0:
                    continue
                if i == 0:
                    # pre-dispatched at stage A (sel == rec.sel0 by
                    # construction: the jump mask is identical)
                    probs_d, dprob_d = handles
                else:
                    (probs_d, dprob_d), _ = self._dispatch_level(
                        i, feats(i), sel, t)
                with TraceAnnotation("ocl.wait", tick=t, level=i):
                    probs_np = np.asarray(probs_d)[:sel.size]
                    dprob_np = np.asarray(dprob_d)[:sel.size]
                eval_mask[i, sel] = True
                dprob_h[i, sel] = dprob_np
                probs_h[i, sel] = probs_np
                if cfg.sample_actions:
                    defer_np = u_act[i, sel] < dprob_np
                else:
                    defer_np = dprob_np > 0.5
                if not budget_ok and i == nlev - 1:
                    defer_np[:] = False     # budget gate: cannot reach expert
                take = sel[~defer_np]
                predictions[take] = np.argmax(probs_np[~defer_np], axis=-1)
                exit_level[take] = i
                alive[take] = False

            want = jumped | alive               # deferred past the last level
            level_costs = np.array([lvl.spec.cost for lvl in self.levels])
            cost_h = eval_mask.T @ level_costs  # sum of evaluated level costs

            # hard budget at tick granularity: first `remaining` lanes win
            called = want.copy()
            hb = cfg.hard_budget
            if hb is not None:
                remaining = max(hb - self.expert_calls_total, 0)
                if int(called.sum()) > remaining:
                    idx_want = np.flatnonzero(called)
                    called[idx_want[remaining:]] = False
            overflow = want & ~called
            span.set_metadata(called=int(called.sum()))

            for s in np.flatnonzero(overflow):
                # budget overflow: fall back to the last student, like the
                # reference's exhausted-budget path (rare; never at S == 1).
                # The fallback forward is real compute and is costed as an
                # evaluation of the last level, identically to the
                # sequential reference; the lane is counted as a last-level
                # exit even if it jumped earlier
                lvl = self.levels[-1]
                x = jnp.asarray(feats(nlev - 1)[s])
                with TraceAnnotation("ocl.wait", tick=t, level=nlev - 1):
                    probs = np.asarray(lvl._predict(lvl.params, x))
                predictions[s] = int(np.argmax(probs))

            levels_out = np.where(called, nlev,
                                  np.where(overflow, nlev - 1, exit_level))
            cost_out = (cost_h + np.where(called, cfg.expert_cost, 0.0)
                        + np.where(overflow, self.levels[-1].spec.cost, 0.0))

            y_full = np.zeros(S, np.int32)
            resolved = False
            prec = None
            if called.any():
                sel_c = np.flatnonzero(called)

                # the update only reads the called lanes' rows (others are
                # dropped by the scatter), so for levels the route never
                # featurized, hash just those k docs instead of all S
                def scatter_feats(i):
                    if feats_cache[i] is not None:
                        return feats_cache[i]
                    lvl = self.levels[i]
                    arr = np.zeros((S,) + lvl.cache_x.shape[1:],
                                   lvl.cache_x.dtype)
                    with TraceAnnotation("ocl.featurize", tick=t, level=i,
                                         rows=sel_c.size):
                        for s in sel_c:
                            arr[s] = lvl.featurize(docs[s])
                    feats_cache[i] = arr
                    return arr

                # every annotated lane calibrates EVERY gate
                # (core.deferral): levels the route never evaluated for a
                # called lane (DAgger jumps short-circuit the walk) get
                # probs/dprob computed at route time against the tick's
                # pre-update students — the same values the synchronous
                # engine computes after its expert call (no update can land
                # in between), and what the deferred lanes' provisional
                # predictions read from
                for i, lvl in enumerate(self.levels):
                    missing = np.flatnonzero(called & ~eval_mask[i])
                    if missing.size == 0:
                        continue
                    (probs_d, dprob_d), _ = self._dispatch_level(
                        i, scatter_feats(i), missing, t, calib=1)
                    n_m = missing.size
                    with TraceAnnotation("ocl.wait", tick=t, level=i):
                        probs_h[i, missing] = np.asarray(probs_d)[:n_m]
                        dprob_h[i, missing] = np.asarray(dprob_d)[:n_m]

                idxs_c = [rec.indices[s] for s in sel_c]
                docs_c = [docs[s] for s in sel_c]
                with TraceAnnotation("ocl.expert", tick=t, rows=sel_c.size):
                    ticket = self._expert_submit(idxs_c, docs_c)
                prec = _PendingTick(
                    ticket=ticket, t=t, called=called.copy(), sel_c=sel_c,
                    feats=[scatter_feats(i) for i in range(nlev)],
                    probs=probs_h, dprob=dprob_h, cache_rngs=cache_rngs,
                    lane_cache_rngs=(
                        [rec.lane_cache[s] for s in sel_c]
                        if self.per_lane else None),
                    lanes=rec.lanes,
                    wall=time.perf_counter(),
                    idxs=idxs_c, docs_k=docs_c)
                if self.max_delay == 0:
                    # synchronous path: resolve inline — with the identical
                    # op sequence as ever (bitwise parity contract).  The
                    # requeue-aware resolve means a fault here heals or
                    # degrades exactly like a deferred commit would; -1
                    # marks an annotation dropped past max_requeues, whose
                    # lane keeps the last student's provisional answer
                    y_lab = self._resolve_labels(prec, 0, sel_c.size)
                    y_full[sel_c] = y_lab
                    predictions[sel_c] = np.where(
                        y_lab >= 0, y_lab,
                        np.argmax(probs_h[nlev - 1, sel_c], axis=-1))
                    resolved = True
                else:
                    # deferred lanes emit the LAST student's prediction
                    # provisionally; the annotation lands max_delay ticks
                    # later.  The probs are the route-time calibration
                    # forwards — no extra serving compute
                    predictions[sel_c] = np.argmax(
                        probs_h[nlev - 1, sel_c], axis=-1)

            if prec is not None:
                self._pending.append(prec)
            # bounded annotation delay, measured in TICKS (not in
            # expert-calling ticks): a record routed at tick u commits at the
            # end of tick u + max_delay even if no intervening tick called
            # the expert — otherwise the converged regime's trickle
            # annotations (the PR-2 beta-floor calibration signal) could be
            # starved for arbitrarily many ticks.  Blocks on the expert if it
            # is slower than max_delay ticks of student compute —
            # deterministic for any expert latency.  Per-lane mode drains on
            # the finer lanes_due sub-deadline schedule instead of whole
            # ticks at age D (see _drain_due).
            self._drain_due(t)

            # sync the observable beta to the value the dispatch-time
            # recurrence produced for this tick (see _route_dispatch — one
            # schedule, computed once)
            for lvl, b in zip(self.levels, rec.beta_after):
                lvl.beta = b

            # per-stream accounting, at the physical lanes this tick occupied
            lanes = np.arange(S) if rec.lanes is None else rec.lanes
            J_t = cfg.mu * cost_out
            self.expert_calls[lanes] += called.astype(np.int64)
            self.total_cost[lanes] += cost_out
            self.level_counts[lanes, levels_out] += 1
            self.items_seen[lanes] += 1
            self.J_cum[lanes] += J_t
            if self.history is not None:
                self.history["level"].append(levels_out.copy())
                self.history["pred"].append(predictions.astype(np.int64))
                self.history["expert_called"].append(called.copy())
                self.history["cost"].append(cost_out.copy())
                self.history["J"].append(J_t.copy())
            if _san.determinism_on() and rec.u_jump_raw is not None:
                # determinism-sanitizer trace: one record per resolved tick,
                # after this tick's due commits — a deterministic point of
                # the schedule, so traces from any worker count / pipeline
                # depth / mesh placement are comparable tick-by-tick
                _san.record_tick(
                    self, t=t, level=levels_out, called=called,
                    pred=predictions, u_jump=rec.u_jump_raw, u_act=u_act,
                    cache_n=self._cache_n, cache_ptr=self._cache_ptr,
                    levels=self.levels)
            return {
                # which stream items this tick served (pipelined callers map
                # late-resolving outputs back to their submission)
                "indices": np.asarray(rec.indices, np.int64),
                "tick": t,
                # physical lane per position (the occupancy identity when the
                # tick was submitted without lanes=)
                "lanes": lanes.copy(),
                "predictions": predictions.astype(np.int64),
                "levels": levels_out,
                "expert_called": called,
                "cost_units": cost_out,
                # annotations still in flight (max_delay >= 1) report -1;
                # they land at commit time, never in a tick's output
                "expert_labels": (np.where(called, y_full,
                                           np.int32(-1)).astype(np.int32)
                                  if resolved else np.full(S, -1, np.int32)),
            }

    # -- commit: apply routed ticks' landed annotations ------------------
    def _drain_due(self, t: int) -> None:
        """Commit every annotation whose deadline has passed by the end
        of tick ``t``, in strict (submit-tick, lane) order.

        The queue head is drained up to its ``lanes_due`` cursor; the
        drain only advances to the next record once the head is FULLY
        committed (so a younger tick's early sub-deadlines never leapfrog
        an older tick's late ones — the deterministic global order the
        per-lane exactness contract rests on).  The head at age
        ``max_delay`` always commits fully, so the bound holds for every
        record.

        ``readiness_commits=True`` additionally commits the head
        record's lanes as soon as their annotations have LANDED (before
        their ``lanes_due`` sub-deadline): per-lane mode extends the due
        cursor by the ready prefix, per-tick mode commits the whole head
        once its ticket reports done.  FIFO (tick, lane) order is
        untouched — only commit *timing* moves, so commit age drops
        while the <= D bound and the exactly-once guarantee still hold;
        the trade is that state now evolves with annotation latency
        (the opt-in documented in the module docstring; the default
        schedule stays bitwise latency-invariant)."""
        while self._pending:
            rec = self._pending[0]
            k = rec.sel_c.size
            due = lanes_due(k, t - rec.t, self.max_delay, self.per_lane)
            if self.readiness_commits and due < k:
                due = max(due, self._ready_count(rec))
            if due > rec.committed:
                if self.per_lane:
                    for j in range(rec.committed, due):
                        self._commit_lane(rec, j, t)
                else:
                    self._commit(rec, t)
            if rec.committed < k:
                break
            self._pending.popleft()

    def _ready_count(self, rec: _PendingTick) -> int:
        """Lanes of the head record committable right now because their
        annotations already landed (readiness-commit mode).  Per-lane:
        the contiguous ready prefix from the commit cursor (a later
        ready lane still waits for earlier ones — FIFO); per-tick: all
        or nothing on whole-ticket completion.  A hung (injected
        "timeout") shard simply never reports ready — its lanes fall
        back to the deadline path, which requeues or drops."""
        k = rec.sel_c.size
        if not self.per_lane:
            return k if rec.ticket.done() else 0
        j = rec.committed
        while j < k and rec.ticket.item_done(j):
            j += 1
        return j

    def _record_commit(self, rec: _PendingTick, lanes, t: int) -> None:
        """Aggregate per-lane commit age/latency stats (and the per-lane
        commit log when enabled).  ``lanes`` are tick POSITIONS; the log
        records the physical lane each position occupied at submit, so
        readers (the admission front-end's per-stream records) can map a
        commit back to the stream that was on that lane at ``rec.t``."""
        n = len(lanes)
        self.commit_stats["lanes"] += n
        self.commit_stats["age_sum"] += n * (t - rec.t)
        self.commit_stats["age_max"] = max(self.commit_stats["age_max"],
                                           t - rec.t)
        self.commit_stats["wall_sum"] += n * (time.perf_counter()
                                              - rec.wall)
        if self.commit_log is not None:
            if rec.lanes is None:
                self.commit_log.extend((rec.t, int(s), t) for s in lanes)
            else:
                self.commit_log.extend(
                    (rec.t, int(rec.lanes[int(s)]), t) for s in lanes)

    def _own_state(self) -> None:
        """Make ``self._owned`` the levels' learned state, donatable.

        The update program donates the state it is given, so it is given
        only trees its own last call returned.  A level whose state came
        from anywhere else (construction, ``reset()``, a restore, a
        caller's install) is first copied to private device buffers: the
        arrays installed from outside outlive the commit."""
        owned = self._owned or (None,) * len(self.levels)
        state, copied = [], False
        for lvl, mine in zip(self.levels, owned):
            cur = tuple(getattr(lvl, attr) for attr in STATE_ATTRS)
            if mine is None or any(a is not b for a, b in zip(cur, mine)):
                cur = jax.tree.map(jnp.copy, cur)
                copied = True
            state.append(cur)
        self._owned = tuple(state)
        self.commit_stats["private_copies"] += copied

    def _commit(self, rec: _PendingTick, t: Optional[int] = None) -> None:
        """Apply a routed tick's expert annotations: ring-buffer scatter
        plus the per-tick weighted student/deferral updates, exactly the
        synchronous engine's update block replayed in FIFO tick order
        with the tick's own cache-sampling generators."""
        at = self.t if t is None else t
        with TraceAnnotation("ocl.commit", tick=rec.t, at=at) as span:
            cfg = self.cfg
            nlev = len(self.levels)
            sel_c = rec.sel_c
            k = sel_c.size
            y_sel = self._resolve_labels(rec, 0, k)
            # -1 marks annotations dropped after max_requeues: those lanes
            # contribute no demonstration — no cache insert, zero update
            # weight, no commit record (the drop was already counted in
            # fault_stats at force-resolve time).  In a fault-free run
            # ok is all-True and this block is bitwise the original path.
            ok = y_sel >= 0
            k_ok = int(ok.sum())
            span.set_metadata(rows=k_ok)
            if k_ok == 0:
                rec.committed = k
                return
            called_eff = rec.called
            if k_ok < k:
                called_eff = rec.called.copy()
                called_eff[sel_c[~ok]] = False
            S = rec.called.shape[0]
            y_full = np.zeros(S, np.int32)
            y_full[sel_c] = np.maximum(y_sel, 0)

            # the tick's host inputs, by name, for the update program
            named = [(f"feats{i}", rec.feats[i]) for i in range(nlev)]
            named += [("y_full", y_full),
                      ("called", called_eff.astype(np.int32)),
                      ("ptr", np.asarray(self._cache_ptr, np.int32))]
            # host mirrors first: sampling sees the post-insert fill level
            for i, lvl in enumerate(self.levels):
                size = lvl.spec.cache_size
                self._cache_n[i] = min(self._cache_n[i] + k_ok, size)
                self._cache_ptr[i] = (self._cache_ptr[i] + k_ok) % size
                with TraceAnnotation("ocl.sample", tick=rec.t, level=i):
                    named.append((f"idx{i}", sample_cache_indices(
                        rec.cache_rngs[i], self._cache_n[i],
                        self._bs_list[i]).astype(np.int32)))
            # reach[l] = prod_{k<l} dprob[k], float32 left fold like the
            # reference's running product
            reach = np.ones((nlev, S), np.float32)
            for i in range(1, nlev):
                reach[i] = reach[i - 1] * rec.dprob[i - 1]
            # the deferral steps' lanes, padded to the tick's bucket
            B_c = self._bucket(k)
            y_b = np.zeros(B_c, np.int32)
            y_b[:k] = np.maximum(y_sel, 0)
            w_b = np.zeros(B_c, np.float32)
            w_b[:k] = ok.astype(np.float32)
            named += [("y", y_b), ("dw", w_b)]
            for i in range(nlev):
                probs_b = np.zeros((B_c, cfg.n_classes), np.float32)
                probs_b[:k] = rec.probs[i, sel_c]
                reach_b = np.zeros(B_c, np.float32)
                reach_b[:k] = reach[i, sel_c]
                # the student weights are an input, as they are to the
                # reference's step, so XLA compiles the same weighted
                # mean and does not fold a constant into it
                named += [(f"w{i}", np.ones(self._bs_list[i], np.float32)),
                          (f"probs{i}", probs_b), (f"reach{i}", reach_b)]
            if self.updates_per_tick == "scaled" and k_ok > 1:
                named.append(("k", np.float32(k_ok)))
            with TraceAnnotation("ocl.update", tick=rec.t, step="commit"):
                bufs, layout = _pack(named)
                self._own_state()
                new_cx, new_cy, new_state = self._update(
                    tuple(self._cache_x), tuple(self._cache_y), self._owned,
                    tuple(self._put_rep(b) for b in bufs), layout)
            self._cache_x = list(new_cx)
            self._cache_y = list(new_cy)
            self._owned = new_state
            for lvl, st in zip(self.levels, new_state):
                for attr, v in zip(STATE_ATTRS, st):
                    setattr(lvl, attr, v)
            self.commit_stats["programs"] += 1
            rec.committed = k
            self._record_commit(rec, sel_c[ok], at)
            # params/dparams changed: any route forward dispatched before
            # this commit is stale (the pipeline's resolve checks and
            # refetches against the new state)
            self._state_version += 1

    def _commit_lane(self, rec: _PendingTick, j: int, t: int) -> None:
        """Apply ONE lane's landed annotation (per-lane commit mode).

        The sequential reference's per-item update block, replayed for
        called lane ``sel_c[j]`` of the tick routed at ``rec.t``:
        single-demonstration ring-buffer scatter into every level, one
        student step on a cache mini-batch sampled with the lane's own
        tick generators, and a single-item deferral/gate update — all
        through the same jitted callables as every other path.  Blocks
        only on the ticket shard holding item ``j`` (``result_slice``);
        earlier lanes of the record have already committed (the drain
        advances ``committed`` strictly in lane order)."""
        with TraceAnnotation("ocl.commit", tick=rec.t, at=t) as span:
            cfg = self.cfg
            nlev = len(self.levels)
            s = int(rec.sel_c[j])
            y = self._resolve_labels(rec, j, j + 1)
            span.set_metadata(rows=int(y[0] >= 0))
            if y[0] < 0:
                # annotation dropped past max_requeues: no demonstration to
                # apply — just advance the cursor (the drop was counted in
                # fault_stats; no commit record, no state change)
                rec.committed = j + 1
                return
            S = rec.called.shape[0]
            y_full = np.zeros(S, np.int32)
            y_full[s] = y[0]
            called_one = np.zeros(S, bool)
            called_one[s] = True
            ptr_pre = np.asarray(self._cache_ptr, np.int32)
            idx_t = []
            rngs = rec.lane_cache_rngs[j]
            for i, lvl in enumerate(self.levels):
                size = lvl.spec.cache_size
                self._cache_n[i] = min(self._cache_n[i] + 1, size)
                self._cache_ptr[i] = (self._cache_ptr[i] + 1) % size
                with TraceAnnotation("ocl.sample", tick=rec.t, level=i):
                    idx_t.append(jnp.asarray(sample_cache_indices(
                        rngs[i], self._cache_n[i],
                        self._bs_list[i]).astype(np.int32)))
            with TraceAnnotation("ocl.update", tick=rec.t,
                                 step="cache_scatter"):
                if rec.feats_dev is None:
                    # the tick's feature rows are shared by all its
                    # per-lane scatters — upload once per record, not
                    # once per lane
                    rec.feats_dev = [self._put_lane(rec.feats[i])
                                     for i in range(nlev)]
                new_cx, new_cy = self._scatter(
                    tuple(self._cache_x), tuple(self._cache_y),
                    tuple(rec.feats_dev),
                    self._put_lane(y_full), self._put_lane(called_one),
                    jnp.asarray(ptr_pre))
            self._cache_x = list(new_cx)
            self._cache_y = list(new_cy)
            # reach[l] = prod_{k<l} dprob[k] at this lane, float32 left
            # fold like the reference's running product
            reach = np.float32(1.0)
            B_c = self._bucket(1)
            for i, lvl in enumerate(self.levels):
                kind = lvl.spec.kind
                with TraceAnnotation("ocl.update", tick=rec.t, level=i,
                                     step=f"{kind}.student_step"):
                    xb = self._cache_x[i][idx_t[i]]
                    yb = self._cache_y[i][idx_t[i]]
                    w = jnp.ones((self._bs_list[i],), jnp.float32)
                    lvl.apply_student_update(xb, yb, w)
                with TraceAnnotation("ocl.update", tick=rec.t, level=i,
                                     step=f"{kind}.deferral_step"):
                    probs_b = np.zeros((B_c, cfg.n_classes), np.float32)
                    probs_b[0] = rec.probs[i, s]
                    y_b = np.zeros(B_c, np.int32)
                    y_b[0] = y[0]
                    reach_b = np.zeros(B_c, np.float32)
                    reach_b[0] = reach
                    w_b = np.zeros(B_c, np.float32)
                    w_b[0] = 1.0
                    lvl.apply_deferral_update(
                        self._put_lane(probs_b), self._put_lane(y_b),
                        self._put_lane(reach_b), self._put_lane(w_b))
                reach = np.float32(reach * np.float32(rec.dprob[i, s]))
            rec.committed = j + 1
            self._record_commit(rec, [s], t)
            self._state_version += 1

    def flush(self) -> int:
        """Drain the deferred-annotation queue (blocking): apply every
        routed tick's pending updates.  Called by ``run`` at stream end;
        servers should call it before checkpointing or idling.  Returns
        the number of ticks committed.

        The route ring must be empty first (``drain()`` — whose outputs
        the caller needs anyway): committing annotations while ticks are
        still in flight would land updates out of FIFO tick order and
        break the pipelined exactness contract."""
        if self._ring:
            raise RuntimeError(
                "route pipeline has in-flight ticks: drain() them "
                "(and consume their outputs) before flush()")
        n = 0
        while self._pending:
            rec = self._pending.popleft()
            if self.per_lane:
                for j in range(rec.committed, rec.sel_c.size):
                    self._commit_lane(rec, j, self.t)
            else:
                self._commit(rec, self.t)
            n += 1
        return n

    # -- live-state checkpointing (ARCHITECTURE.md §10) ------------------
    def _fingerprint(self) -> dict:
        """Config facts a checkpoint must agree on to be restorable."""
        return {
            "engine": "batched", "ckpt_version": _CKPT_VERSION,
            "n_streams": self.n_streams, "n_levels": len(self.levels),
            "max_delay": self.max_delay, "per_lane": self.per_lane,
            "updates_per_tick": self.updates_per_tick,
            "seed": self.cfg.seed, "n_classes": self.cfg.n_classes,
        }

    def save_state(self, path: str) -> str:
        """Checkpoint the engine's full live state mid-stream.

        Captures per-level STATE_ATTRS (params, optimizer state,
        deferral MLPs) and gates (betas), the demonstration ring
        buffers, per-lane accounting, the route-time beta/item
        recurrence, commit stats/log, fault + fleet stats, and the
        pending deferred-annotation queue — including each pending
        record's exact mid-consumption cache-generator states, so a
        restored engine replays the remaining commits with the very
        draws the uninterrupted run would use (the bitwise resume
        contract, tests/test_checkpoint.py).  Uncommitted annotations
        are resolved here (blocking, under the requeue/timeout
        discipline) so the checkpoint never holds an unresolvable
        ticket.  The route ring must be drained first, like ``flush``.
        """
        if self._ring:
            raise RuntimeError(
                "route pipeline has in-flight ticks: drain() them "
                "(and consume their outputs) before save_state()")
        from repro.checkpoint import save_checkpoint
        nlev = len(self.levels)
        tree = {
            "levels": [lvl.state_tree() for lvl in self.levels],
            "cache_x": [np.asarray(jax.device_get(x))
                        for x in self._cache_x],
            "cache_y": [np.asarray(jax.device_get(y))
                        for y in self._cache_y],
            "acct": {
                "expert_calls": self.expert_calls,
                "total_cost": self.total_cost,
                "level_counts": self.level_counts,
                "items_seen": self.items_seen,
                "J_cum": self.J_cum,
            },
        }
        pending_meta = []
        for r_i, rec in enumerate(list(self._pending)):
            k = rec.sel_c.size
            labels = np.full(k, -1, np.int32)
            if rec.committed < k:
                labels[rec.committed:] = self._resolve_labels(
                    rec, rec.committed, k)
            entry = {
                "called": rec.called, "sel_c": rec.sel_c,
                "labels": labels, "probs": rec.probs, "dprob": rec.dprob,
                "feats": list(rec.feats),
                "idxs": np.asarray(rec.idxs or [], np.int64),
            }
            if rec.lanes is not None:
                entry["lanes"] = rec.lanes
            tree[f"pending{r_i}"] = entry
            pending_meta.append({
                "t": rec.t, "committed": rec.committed,
                "has_lanes": rec.lanes is not None,
                "requeues": {str(lo): n
                             for lo, n in rec.requeues.items()},
                "cache_rngs": [generator_state(g)
                               for g in rec.cache_rngs],
                "lane_cache_rngs": (
                    [[generator_state(g) for g in lane]
                     for lane in rec.lane_cache_rngs]
                    if rec.lane_cache_rngs is not None else None),
            })
        meta = {
            **self._fingerprint(),
            "t": self.t,
            "beta": [float(lvl.beta) for lvl in self.levels],
            "cache_n": list(self._cache_n),
            "cache_ptr": list(self._cache_ptr),
            "route_beta": [float(b) for b in self._route_beta],
            "route_items": self._route_items,
            "commit_stats": dict(self.commit_stats),
            "commit_log": ([list(e) for e in self.commit_log]
                           if self.commit_log is not None else None),
            "pipeline_stats": dict(self.pipeline_stats),
            "fault_stats": dict(self.fault_stats),
            "fleet_log": [list(e) for e in self.fleet_log],
            "n_pending": len(self._pending),
            "pending": pending_meta,
        }
        assert nlev == len(tree["levels"])
        return save_checkpoint(path, tree, meta)

    def restore_state(self, path: str) -> None:
        """Restore a ``save_state`` checkpoint into this (freshly
        constructed, same-config) engine; raises ``CheckpointError`` on
        a config mismatch.  The resumed run is bitwise identical to the
        uninterrupted one from the checkpoint tick onward."""
        from repro.checkpoint import CheckpointError, restore_checkpoint
        tree, meta = restore_checkpoint(path)
        for key, val in self._fingerprint().items():
            if meta.get(key) != val:
                raise CheckpointError(
                    f"checkpoint/engine mismatch on {key}: checkpoint "
                    f"has {meta.get(key)!r}, engine has {val!r}")
        for lvl, st, b in zip(self.levels, tree["levels"], meta["beta"]):
            lvl.load_state_tree(st, put=self._put_rep)
            lvl.beta = float(b)
        self._cache_x = [self._put_rep(np.asarray(x))
                         for x in tree["cache_x"]]
        self._cache_y = [self._put_rep(np.asarray(y))
                         for y in tree["cache_y"]]
        self._cache_n = [int(v) for v in meta["cache_n"]]
        self._cache_ptr = [int(v) for v in meta["cache_ptr"]]
        acct = tree["acct"]
        self.expert_calls[:] = np.asarray(acct["expert_calls"])
        self.total_cost[:] = np.asarray(acct["total_cost"])
        self.level_counts[:] = np.asarray(acct["level_counts"])
        self.items_seen[:] = np.asarray(acct["items_seen"])
        self.J_cum[:] = np.asarray(acct["J_cum"])
        self.t = int(meta["t"])
        self._route_beta = [float(b) for b in meta["route_beta"]]
        self._route_items = int(meta["route_items"])
        cs = meta["commit_stats"]
        self.commit_stats = {k: type(v)(cs.get(k, v))
                             for k, v in _zero_commit_stats().items()}
        self.commit_log = ([tuple(e) for e in meta["commit_log"]]
                           if meta["commit_log"] is not None else None)
        self.pipeline_stats = {k: int(v)
                               for k, v in meta["pipeline_stats"].items()}
        self.fault_stats = {k: int(v)
                            for k, v in meta["fault_stats"].items()}
        self.fleet_log = [tuple(int(x) for x in e)
                          for e in meta["fleet_log"]]
        self._pending.clear()
        for r_i, pm in enumerate(meta["pending"]):
            pt = tree[f"pending{r_i}"]
            self._pending.append(_PendingTick(
                # the ticket was resolved at save time (labels hold the
                # -1 sentinel where annotations were dropped), so the
                # restored record never needs docs for a requeue
                ticket=ExpertTicket(
                    labels=np.asarray(pt["labels"], np.int32)),
                t=int(pm["t"]),
                called=np.asarray(pt["called"], bool),
                sel_c=np.asarray(pt["sel_c"], np.int64),
                feats=[np.asarray(f) for f in pt["feats"]],
                probs=np.asarray(pt["probs"], np.float32),
                dprob=np.asarray(pt["dprob"], np.float32),
                cache_rngs=[generator_from_state(s)
                            for s in pm["cache_rngs"]],
                committed=int(pm["committed"]),
                lane_cache_rngs=(
                    [[generator_from_state(s) for s in lane]
                     for lane in pm["lane_cache_rngs"]]
                    if pm["lane_cache_rngs"] is not None else None),
                lanes=(np.asarray(pt["lanes"], np.int64)
                       if pm["has_lanes"] else None),
                wall=time.perf_counter(),
                idxs=[int(i) for i in np.asarray(pt["idxs"])],
                docs_k=None,
                requeues={int(lo): int(n)
                          for lo, n in pm["requeues"].items()}))
        # restored params invalidate anything dispatched before (there
        # is nothing in flight, but a later pipelined dispatch must not
        # compare equal to a pre-restore version)
        self._state_version += 1

    # -- per-stream metrics ---------------------------------------------
    def stream_metrics(self) -> dict:
        """Independent per-lane accounting (S rows each)."""
        seen = np.maximum(self.items_seen, 1)[:, None]
        return {
            "expert_calls": self.expert_calls.copy(),
            "items_seen": self.items_seen.copy(),
            "level_fractions": self.level_counts / seen,
            "total_cost_units": self.total_cost.copy(),
            "J_cum": self.J_cum.copy(),
        }

    # -- conveniences ----------------------------------------------------
    def run(self, stream, log_every: int = 0,
            checkpoint_every: int = 0,
            checkpoint_path: Optional[str] = None) -> dict:
        """Serve an entire stream, tick-major: tick T covers items
        [T*S, T*S + S) with lane s = offset.  Returns OnlineCascade-style
        summary metrics plus throughput and per-stream accounting.

        With ``pipeline_depth >= 1`` the loop drives
        ``submit_tick``/``drain`` — results land up to P ticks after
        submission and are mapped back through each output's "indices";
        with depth 0 it is the classic one-``process_tick``-per-tick
        loop.

        ``checkpoint_every=k`` saves live state to ``checkpoint_path``
        every k ticks (draining the route ring first — save_state's
        precondition).  On an engine that already holds restored state
        (``restore_state``), serving resumes at item ``self.t * S`` —
        the tick-major identity — and metrics cover the items this call
        served."""
        S = self.n_streams
        n = len(stream)
        preds = np.zeros(n, np.int32)
        done = 0                      # items with results already landed
        first = self.t * S            # 0 on a fresh engine; resume point
                                      # on a restored one

        def take(out):
            nonlocal done
            idxs = out["indices"]
            preds[idxs] = out["predictions"]
            done = max(done, int(idxs.max()) + 1) if idxs.size else done

        t0 = time.perf_counter()
        for start in range(first, n, S):
            stop = min(start + S, n)
            idxs = list(range(start, stop))
            docs = [stream.docs[i] for i in idxs]
            if self.pipeline_depth:
                for out in self.submit_tick(idxs, docs):
                    take(out)
            else:
                take(self.process_tick(idxs, docs))
            if (log_every and done
                    and (stop // log_every) > (start // log_every)):
                lo = min(first, done)
                acc = float(np.mean(preds[lo:done]
                                    == stream.labels[lo:done]))
                print(f"[{done}/{n}] acc={acc:.4f} "
                      f"expert_calls={self.expert_calls_total}")
            if (checkpoint_every and checkpoint_path
                    and self.t % checkpoint_every == 0 and stop < n):
                for out in self.drain():
                    take(out)
                self.save_state(checkpoint_path)
        for out in self.drain():
            take(out)
        self.flush()
        dt = time.perf_counter() - t0
        labels = stream.labels
        served = n - first
        acc = float(np.mean(preds[first:] == labels[first:]))
        metrics = {
            "accuracy": acc,
            "expert_calls": self.expert_calls_total,
            "total_cost_units": float(self.total_cost.sum()),
            "level_fractions": (self.level_counts.sum(axis=0)
                                / max(n, 1)).tolist(),
            "predictions": preds,
            "items_per_sec": served / max(dt, 1e-9),
            "per_stream": self.stream_metrics(),
        }
        return metrics
