"""Expert models m_N for the cascade.

* ``SimulatedExpert`` — the default for paper-reproduction runs: returns the
  stream's precomputed expert annotations (ground truth corrupted at the
  paper's per-dataset LLM accuracy, length-biased; data.streams).  Zero
  compute, exact control of the noisy-teacher regime.
* ``ModelExpert`` — a real in-repo model: a transformer classifier trained
  offline on ground truth to stand in for a zero-shot LLM.  Used by the
  end-to-end example so the full pipeline (featurize -> students -> deferral
  -> expert forward -> online updates) exercises real compute.

Async annotation interface (``submit``/``submit_many``/``poll``)
----------------------------------------------------------------
At serving scale the expert forward is the latency wall, so both experts
expose a two-phase interface the batched engine's deferred-lane queue
drives (core/batched.py ``max_delay``):

  ``ticket = expert.submit(idxs, docs)``        # one batch, one request
  ``ticket = expert.submit_many(idxs, docs)``   # sharded over the pool
  ``labels = expert.poll(ticket)``              # block until ALL done
  ``expert.poll(ticket, block=False)``          # None while in flight
  ``expert.poll_partial(ticket)``               # (ready_mask, labels)

``submit_many`` splits the batch into ``min(workers, k)`` contiguous
shards (``shard_bounds`` — a pure function of (k, workers), never of
worker timing) and annotates them on W concurrent workers; the returned
``ExpertTicket`` tracks **per-item completion**, so the engine's
per-lane commit drain (``BatchedCascadeEngine(per_lane=True)``) can
block on exactly the prefix it needs (``result_slice``) instead of the
whole batch.  ``SimulatedExpert`` resolves labels lazily *at poll time*
(never at submit — an optional fake latency, counted in non-blocking
``done()`` probes, makes its tickets genuinely in-flight so delay/pool
tests exercise the real poll path).  ``ModelExpert`` runs each shard's
batched forward on a pool thread, so the host-side expert compute
overlaps the engine's next-tick student compute; jitted JAX dispatch is
thread-safe and releases the GIL while the device executes.  Either way
a ticket resolves to exactly the labels ``label_batch`` would have
returned synchronously on each shard — delay and worker count never
change annotations for the table-lookup expert, and are deterministic
functions of (k, workers) for the model expert.

Failure semantics (ARCHITECTURE.md §10)
---------------------------------------
A shard that fails to resolve raises a typed error carrying its item
range: ``ExpertShardTimeout`` when ``result_slice(..., timeout=)``
expires, ``ExpertWorkerDied`` when the worker raised or its process
vanished.  The engine reacts by *requeuing* the failed range to another
worker (``ExpertTicket.replace`` splices a fresh sub-ticket over the
dead shard), or — past ``max_requeues`` — by force-resolving it to the
``-1`` dropped-annotation sentinel (``force_resolve``) so commits never
deadlock.  ``FlakyExpert`` wraps any expert with scripted or seeded
fault injection (timeout / worker-death / slow-shard schedules) so the
chaos tests and ``benchmarks/fault_tolerance.py`` share one fault
model.  ``ModelExpert(backend="process")`` runs shard forwards in a
spawn-context process pool for GIL-bound annotators; a broken pool is
detected and rebuilt on the next submit, which is what turns a real
worker death into an ``ExpertWorkerDied`` + successful requeue.
"""
from __future__ import annotations

import threading
import zlib
from concurrent.futures import (ProcessPoolExecutor, ThreadPoolExecutor,
                                TimeoutError as _FuturesTimeout)
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis import sanitize as _san
from repro.data.features import hash_ids
from repro.data.streams import Stream
from repro.models.students import (
    TinyTFSpec, tinytf_init, tinytf_loss, tinytf_predict)
from repro.optim import adam


class ExpertShardError(RuntimeError):
    """A ticket shard failed to resolve.

    Carries the failed item range ``[lo, hi)`` (``hi`` is None for a
    legacy future-form shard whose length was never observed — the
    holder of the ticket knows the submitted batch size and substitutes
    it).  The engine's requeue path catches this, never user code on the
    synchronous ``label_batch`` surface.
    """

    def __init__(self, lo: int, hi: Optional[int], msg: str,
                 cause: Optional[BaseException] = None):
        super().__init__(f"{msg} (items [{lo}, {hi}))")
        self.lo = int(lo)
        self.hi = None if hi is None else int(hi)
        self.cause = cause


class ExpertShardTimeout(ExpertShardError):
    """``result_slice(..., timeout=)`` expired before the shard landed."""

    def __init__(self, lo, hi, cause=None):
        super().__init__(lo, hi, "expert shard timed out", cause)


class ExpertWorkerDied(ExpertShardError):
    """The worker annotating a shard raised or its process vanished."""

    def __init__(self, lo, hi, cause=None):
        super().__init__(lo, hi, f"expert worker died: {cause!r}", cause)


def shard_bounds(k: int, workers: int) -> List[Tuple[int, int]]:
    """Contiguous balanced split of ``k`` items into ``min(workers, k)``
    shards: shard j covers ``[j*k//w, (j+1)*k//w)``.

    A pure function of (k, workers) — never of worker timing — so a
    pooled annotation's shard layout (and therefore, for a model expert,
    its per-shard batched forwards) is deterministic.  Contiguous shards
    match the engine's (tick, lane) commit order: the per-lane drain
    blocks on a prefix, which touches the fewest shards possible.
    """
    if k <= 0:
        return []
    w = max(1, min(int(workers), k))
    edges = [(j * k) // w for j in range(w + 1)]
    return [(edges[j], edges[j + 1]) for j in range(w)]


class ExpertTicket:
    """Handle for one in-flight batched annotation request.

    The ticket is a list of contiguous *shards*, each either an already
    resolved ``np.ndarray`` of labels or a future-like object exposing
    ``done()``/``result()`` (``concurrent.futures.Future`` for
    thread-backed experts, ``_SimulatedAnnotation`` for the fake-latency
    simulated expert).  Per-item completion is observable through
    ``item_done``/``ready_mask``, and ``result_slice`` blocks on exactly
    the shards overlapping the requested range — the primitive the
    engine's per-lane commit drain is built on.

    Thread safety: the shard table is mutated in place as shards resolve
    (``_resolve`` swaps a future for its labels, ``_settle_bounds`` fills
    a legacy shard's unknown upper bound), and tickets may be probed
    while pool workers are completing those futures — so every shard
    access goes through ``self._lock`` (re-entrant: the per-item surface
    calls the internals).  cascade-lint CAS004 enforces the enclosure.
    """

    __slots__ = ("_shards", "_lock")

    def __init__(self, labels: Optional[np.ndarray] = None, future=None,
                 shards: Optional[Sequence] = None):
        if sum(x is not None for x in (labels, future, shards)) != 1:
            raise ValueError(
                "exactly one of labels/future/shards required")
        self._lock = threading.RLock()
        if labels is not None:
            labels = np.asarray(labels, np.int32)
            self._shards = [[0, len(labels), labels]]  # guarded-by: _lock
        elif future is not None:
            # length unknown until resolution (legacy single-future form)
            self._shards = [[0, None, future]]
        else:
            # hi None = legacy future-form span (length settles on
            # resolution); preserved so ``wrapped`` round-trips it
            self._shards = [[int(lo), None if hi is None else int(hi),
                             payload] for lo, hi, payload in shards]

    # -- internals ------------------------------------------------------
    def _resolve(self, shard, timeout: Optional[float] = None) -> np.ndarray:
        if not isinstance(shard[2], np.ndarray):
            try:
                # no-timeout waits stay a plain result() call: futures
                # here are duck-typed and need not take a timeout arg
                labels = (shard[2].result() if timeout is None
                          else shard[2].result(timeout))
            except (_FuturesTimeout, TimeoutError) as e:
                raise ExpertShardTimeout(shard[0], shard[1], cause=e) from e
            except ExpertShardError:
                raise
            except Exception as e:
                # anything else out of a future is the worker's demise:
                # an exception it raised, or BrokenProcessPool after its
                # process vanished
                raise ExpertWorkerDied(shard[0], shard[1], cause=e) from e
            shard[2] = np.asarray(labels, np.int32)
            if shard[1] is None:
                shard[1] = shard[0] + len(shard[2])
        return shard[2]

    @staticmethod
    def _shard_done(shard) -> bool:
        return isinstance(shard[2], np.ndarray) or shard[2].done()

    def _settle_bounds(self, shard) -> None:
        """Resolve a shard whose upper bound is unknown (the legacy
        ``future=`` form) once it is done, so per-item queries can
        bound-check without blocking on in-flight work."""
        if shard[1] is None and self._shard_done(shard):
            self._resolve(shard)

    def _n_items(self) -> int:
        with self._lock:
            last = self._shards[-1] if self._shards else None
            if last is None:
                return 0
            self._settle_bounds(last)
            if last[1] is None:
                raise ValueError("ticket length unknown while its legacy "
                                 "future-form shard is still in flight")
            return int(last[1])

    # -- whole-ticket interface (the PR-3 per-tick commit path) ---------
    def done(self) -> bool:
        """True once every item's labels are available without blocking.

        Probes EVERY shard (no short-circuit), so fake-latency shards
        (``_SimulatedAnnotation`` credits) drain uniformly — one credit
        per shard per whole-ticket poll, the same rate ``ready_mask``
        consumes them."""
        with self._lock:
            return all([self._shard_done(s) for s in self._shards])

    def result(self) -> np.ndarray:
        """Block until every shard resolves; return all labels in order."""
        with self._lock:
            if not self._shards:
                return np.zeros((0,), np.int32)
            return np.concatenate([self._resolve(s) for s in self._shards])

    # -- per-item interface (the per-lane commit path) ------------------
    def item_done(self, i: int) -> bool:
        """True once item ``i``'s label is available without blocking.

        Raises IndexError for out-of-range ``i``; while a legacy
        future-form shard is still in flight its length is unknown, so
        indices past its start conservatively report not-done."""
        with self._lock:
            for shard in self._shards:
                self._settle_bounds(shard)
                lo, hi = shard[0], shard[1]
                if lo <= i and (hi is None or i < hi):
                    return self._shard_done(shard)
        raise IndexError(i)

    def ready_mask(self) -> np.ndarray:
        """(n,) bool — which items are resolvable without blocking."""
        with self._lock:
            for shard in self._shards:
                self._settle_bounds(shard)
            mask = np.zeros(self._n_items(), bool)
            for shard in self._shards:
                mask[shard[0]:shard[1]] = self._shard_done(shard)
            return mask

    def result_slice(self, lo: int, hi: int,
                     timeout: Optional[float] = None) -> np.ndarray:
        """Labels for items ``[lo, hi)``, blocking only on the shards
        that overlap the range (other shards stay in flight).

        ``timeout`` bounds the wait on EACH overlapping shard; on expiry
        an ``ExpertShardTimeout`` carrying that shard's range escapes —
        the engine's requeue deadline (core/batched.py).
        """
        parts = []
        with self._lock:
            for s in self._shards:
                s_lo, s_hi = s[0], s[1]
                if s_hi is not None and (s_hi <= lo or s_lo >= hi):
                    continue
                labels = self._resolve(s, timeout)
                s_hi = s[1]
                if s_hi <= lo or s_lo >= hi:
                    continue
                parts.append(labels[max(lo - s_lo, 0):hi - s_lo])
        if not parts:
            return np.zeros((0,), np.int32)
        return np.concatenate(parts)

    # -- failure handling (the engine's requeue path) -------------------
    def _find_shard(self, lo: int, hi: int) -> int:
        with self._lock:      # re-entrant under replace/force_resolve
            for i, s in enumerate(self._shards):
                if s[0] == lo and (s[1] == hi or s[1] is None):
                    return i
        raise ValueError(f"no shard covering exactly [{lo}, {hi})")

    def replace(self, lo: int, hi: int, ticket: "ExpertTicket") -> None:
        """Splice ``ticket`` (a fresh annotation of items ``[lo, hi)``,
        indexed from 0) over the failed shard covering that range —
        the requeue primitive.  The replacement's shards are re-based
        to this ticket's coordinates."""
        with self._lock:
            i = self._find_shard(lo, hi)
            with ticket._lock:
                repl = [[lo + s[0],
                         hi if s[1] is None else lo + s[1],
                         s[2]] for s in ticket._shards]
            self._shards[i:i + 1] = repl

    def force_resolve(self, lo: int, hi: int, labels: np.ndarray) -> None:
        """Overwrite the shard covering ``[lo, hi)`` with fixed labels —
        the graceful-degradation terminal after ``max_requeues`` (the
        engine passes the ``-1`` dropped-annotation sentinel)."""
        with self._lock:
            i = self._find_shard(lo, hi)
            self._shards[i] = [lo, hi, np.asarray(labels, np.int32)]

    def wrapped(self, fn: Callable) -> "ExpertTicket":
        """A new ticket over the same shard spans with each payload
        replaced by ``fn(shard_idx, payload)`` — the fault-injection
        hook ``FlakyExpert`` builds on."""
        with self._lock:
            return ExpertTicket(shards=[
                (s[0], s[1], fn(j, s[2]))
                for j, s in enumerate(self._shards)])


def poll_ticket(ticket: ExpertTicket,
                block: bool = True) -> Optional[np.ndarray]:
    """Shared ``poll`` body: labels when ready, else None (non-blocking)."""
    if not block and not ticket.done():
        return None
    return ticket.result()


def poll_ticket_partial(
        ticket: ExpertTicket) -> Tuple[np.ndarray, np.ndarray]:
    """Non-blocking partial poll: ``(ready_mask, labels)``.

    ``labels[i]`` is valid only where ``ready_mask[i]``; unready slots
    hold -1 (the same in-flight sentinel the engine's tick outputs use).
    """
    mask = ticket.ready_mask()
    labels = np.full(mask.shape, -1, np.int32)
    lo = 0
    while lo < mask.size:
        if not mask[lo]:
            lo += 1
            continue
        hi = lo
        while hi < mask.size and mask[hi]:
            hi += 1
        labels[lo:hi] = ticket.result_slice(lo, hi)
        lo = hi
    return mask, labels


LatencyLike = Union[None, int, Callable[[int, int], int]]


class _SimulatedAnnotation:
    """Future-like shard payload for ``SimulatedExpert``.

    Labels are computed lazily at resolution (``result``), never at
    submit — so the engine's poll path is exercised for real.  The fake
    latency is counted in non-blocking ``done()`` probes: each probe
    consumes one credit, and the shard reports ready once its credits
    run out.  The engine polls once per tick boundary, so a credit is
    roughly one tick of simulated annotation latency.  ``result()``
    always resolves (a blocking poll "waits out" the remaining latency)
    — latency shifts *when* labels are observable, never *what* they
    are.
    """

    __slots__ = ("_fn", "_credits")

    def __init__(self, fn: Callable[[], np.ndarray], credits: int):
        self._fn = fn
        self._credits = max(int(credits), 0)

    def done(self) -> bool:
        if self._credits > 0:
            self._credits -= 1
            return False
        return True

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        # a blocking resolve waits out any remaining latency, so the
        # timeout can never expire on a simulated shard
        self._credits = 0
        return self._fn()


class SimulatedExpert:
    """Zero-compute expert replaying precomputed noisy-LLM labels.

    ``workers`` sets how many shards ``submit_many`` splits a batch into
    (mirroring ``ModelExpert``'s pool so the engine's per-lane drain
    sees the same per-item ticket shape).  ``latency`` simulates
    annotation delay: an int applies to every shard; a callable
    ``(submit_seq, shard_idx) -> int`` scripts adversarial per-shard
    schedules (credits are consumed by non-blocking ``done()`` probes —
    see ``_SimulatedAnnotation``).  Labels are a pure table lookup, so
    they are invariant to workers and latency by construction.
    """

    def __init__(self, stream: Stream, name: str = "gpt-3.5-turbo",
                 cost: float = 1.0e6, *, workers: Union[int, str] = 1,
                 latency: LatencyLike = None):
        self.name = name
        self.cost = cost
        # workers="auto" asks the ENGINE to drive the width off queue
        # depth (core/batched.py autoscale); the fleet starts at 1
        self.auto_workers = workers == "auto"
        self.workers = 1 if self.auto_workers else max(int(workers), 1)
        self.latency = latency
        self._labels = stream.expert_labels(name)
        self._lock = threading.RLock()
        self._submit_seq = 0   # guarded-by: _lock

    def label(self, idx: int, doc: np.ndarray) -> int:
        """Annotate one stream item (table lookup)."""
        return int(self._labels[idx])

    def label_batch(self, idxs, docs) -> np.ndarray:
        """Annotate a deferred batch in one call (zero compute here; the
        batched engine routes all deferrals of a tick through this)."""
        return self._labels[np.asarray(idxs, np.int64)].astype(np.int32)

    # -- async interface ------------------------------------------------
    def _shard_delay(self, seq: int, j: int) -> int:
        lat = self.latency
        if lat is None:
            return 0
        if callable(lat):
            return int(lat(seq, j))
        return int(lat)

    def _make_ticket(self, idxs, docs, nshards: int) -> ExpertTicket:
        idx_arr = np.asarray(idxs, np.int64)
        with self._lock:
            seq = self._submit_seq
            self._submit_seq += 1
        shards = []
        for j, (lo, hi) in enumerate(shard_bounds(len(idx_arr), nshards)):
            sel = idx_arr[lo:hi]
            shards.append((lo, hi, _SimulatedAnnotation(
                lambda sel=sel: self._labels[sel].astype(np.int32),
                self._shard_delay(seq, j))))
        return ExpertTicket(shards=shards)

    def submit(self, idxs, docs) -> ExpertTicket:
        """Enqueue a batch annotation as one lazily-resolving shard."""
        return self._make_ticket(idxs, docs, 1)

    def submit_many(self, idxs, docs) -> ExpertTicket:
        """Enqueue a batch sharded into ``min(workers, k)`` lazily
        resolving sub-requests with per-item completion."""
        return self._make_ticket(idxs, docs, self.workers)

    def poll(self, ticket: ExpertTicket,
             block: bool = True) -> Optional[np.ndarray]:
        """Labels when ready, else None (non-blocking poll)."""
        return poll_ticket(ticket, block)

    def poll_partial(self, ticket: ExpertTicket):
        """Non-blocking partial poll: (ready_mask, labels-with--1)."""
        return poll_ticket_partial(ticket)


def _fault_draw(seed: int, seq: int, shard: int, salt: str) -> float:
    """Deterministic uniform in [0, 1) for one (submit, shard) cell.

    A keyed hash, not a Generator: fault draws must be a pure function
    of the submit sequence so a replayed schedule injects the same
    faults, and constructing RNGs per shard would trip the repo's RNG
    discipline (cascade-lint CAS001) for no benefit.
    """
    h = zlib.crc32(f"{seed}:{seq}:{shard}:{salt}".encode())
    return (h & 0xFFFFFF) / float(1 << 24)


class _FaultyShard:
    """Payload wrapper injecting one scripted fault into a shard.

    * ``"timeout"`` — a hung worker: never reports done, and ``result``
      raises ``TimeoutError`` even on a blocking resolve (so tests and
      the no-timeout engine path stay deadlock-free; the engine treats
      it exactly like an expired deadline).
    * ``"die"`` — the worker crashed: reports done, ``result`` raises.
    * ``("slow", n)`` — adds ``n`` extra not-done probes before
      delegating (per-shard latency skew for readiness/commit-age
      tests).
    """

    __slots__ = ("_inner", "_kind", "_credits")

    def __init__(self, inner, fault):
        if isinstance(fault, tuple):
            kind, credits = fault
        else:
            kind, credits = fault, 0
        if kind not in ("timeout", "die", "slow"):
            raise ValueError(f"unknown fault kind {kind!r}")
        self._inner = inner
        self._kind = kind
        self._credits = max(int(credits), 0)

    def done(self) -> bool:
        if self._kind == "timeout":
            return False
        if self._kind == "die":
            return True
        if self._credits > 0:
            self._credits -= 1
            return False
        return (isinstance(self._inner, np.ndarray)
                or self._inner.done())

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if self._kind == "timeout":
            raise TimeoutError("injected shard timeout (hung worker)")
        if self._kind == "die":
            raise RuntimeError("injected worker death")
        self._credits = 0
        if isinstance(self._inner, np.ndarray):
            return self._inner
        return self._inner.result(timeout)


class FlakyExpert:
    """Fault-injection wrapper around any expert (chaos harness).

    Faults apply per (submit sequence, shard index) cell and are chosen
    either by an explicit ``schedule(seq, shard) -> None | "timeout" |
    "die" | ("slow", n)`` callable, or by seeded per-cell rates
    (``timeout_rate`` / ``death_rate`` / ``slow_rate``, drawn via a
    keyed hash — deterministic, replayable, CAS001-clean).  Requeued
    shards arrive as NEW submits with fresh sequence numbers, so a
    scripted schedule decides whether a retry succeeds or fails again.

    Labels themselves are never altered: a fault only changes *whether
    and when* a shard resolves.  That is what makes the chaos suite's
    bitwise-invariance assertions meaningful — any divergence under
    injected faults is an engine bug, not injected noise (the one
    exception being annotations the engine explicitly drops after
    ``max_requeues``, which it must count in ``dropped_annotation``).
    """

    def __init__(self, inner, *, schedule: Optional[Callable] = None,
                 timeout_rate: float = 0.0, death_rate: float = 0.0,
                 slow_rate: float = 0.0, slow_credits: int = 2,
                 seed: int = 0):
        self.inner = inner
        self.name = getattr(inner, "name", "flaky")
        self.cost = getattr(inner, "cost", 0.0)
        self.schedule = schedule
        self.timeout_rate = float(timeout_rate)
        self.death_rate = float(death_rate)
        self.slow_rate = float(slow_rate)
        self.slow_credits = int(slow_credits)
        self.seed = int(seed)
        self._lock = threading.RLock()
        self._submit_seq = 0        # guarded-by: _lock
        self.injected = {"timeout": 0, "die": 0, "slow": 0}

    # fleet-width plumbing: autoscale drives the INNER pool through the
    # wrapper, so a flaky fleet still scales
    @property
    def workers(self) -> int:
        return getattr(self.inner, "workers", 1)

    @workers.setter
    def workers(self, w: int) -> None:
        self.inner.workers = w

    @property
    def auto_workers(self) -> bool:
        return getattr(self.inner, "auto_workers", False)

    def label(self, idx, doc):
        """Synchronous single-item surface is passed through un-faulted."""
        return self.inner.label(idx, doc)

    def label_batch(self, idxs, docs):
        """Synchronous batch surface is passed through un-faulted."""
        return self.inner.label_batch(idxs, docs)

    def _fault(self, seq: int, j: int):
        if self.schedule is not None:
            return self.schedule(seq, j)
        if (self.timeout_rate
                and _fault_draw(self.seed, seq, j, "t") < self.timeout_rate):
            return "timeout"
        if (self.death_rate
                and _fault_draw(self.seed, seq, j, "d") < self.death_rate):
            return "die"
        if (self.slow_rate
                and _fault_draw(self.seed, seq, j, "s") < self.slow_rate):
            return ("slow", self.slow_credits)
        return None

    def _wrap(self, ticket: ExpertTicket) -> ExpertTicket:
        with self._lock:
            seq = self._submit_seq
            self._submit_seq += 1

        def inject(j, payload):
            fault = self._fault(seq, j)
            if fault is None:
                return payload
            kind = fault[0] if isinstance(fault, tuple) else fault
            with self._lock:
                self.injected[kind] += 1
            return _FaultyShard(payload, fault)

        return ticket.wrapped(inject)

    def submit(self, idxs, docs) -> ExpertTicket:
        """Submit through the inner expert, then overlay faults."""
        return self._wrap(self.inner.submit(idxs, docs))

    def submit_many(self, idxs, docs) -> ExpertTicket:
        """Sharded submit through the inner expert, faults overlaid."""
        return self._wrap(self.inner.submit_many(idxs, docs))

    def poll(self, ticket: ExpertTicket,
             block: bool = True) -> Optional[np.ndarray]:
        """Labels when ready, else None (non-blocking poll)."""
        return poll_ticket(ticket, block)

    def poll_partial(self, ticket: ExpertTicket):
        """Non-blocking partial poll: (ready_mask, labels-with--1)."""
        return poll_ticket_partial(ticket)

    def close(self) -> None:
        """Close the wrapped expert's pool (if it has one)."""
        close = getattr(self.inner, "close", None)
        if close is not None:
            close()


# -- process-pool worker side (module-level: must pickle under spawn) ---
_PROCESS_EXPERT: Optional[list] = None


def _process_worker_init(params, spec) -> None:
    """Pool initializer: stash (host params, spec); jit lazily per child."""
    global _PROCESS_EXPERT
    _PROCESS_EXPERT = [params, spec, None]


def _process_label_batch(idxs, docs) -> np.ndarray:
    """``ModelExpert.label_batch`` body, run inside a pool process."""
    params, spec, predict = _PROCESS_EXPERT
    if predict is None:
        predict = jax.jit(lambda p, ids: tinytf_predict(p, ids, spec))
        _PROCESS_EXPERT[2] = predict
    if len(docs) == 0:
        return np.zeros((0,), np.int32)
    ids = np.stack([hash_ids(d, spec.vocab, spec.max_len) for d in docs])
    probs = predict(params, jnp.asarray(ids))
    return np.asarray(jnp.argmax(probs, axis=-1), np.int32)


@dataclass
class ModelExpert:
    """A trained transformer classifier acting as the LLM expert.

    ``workers`` sizes the annotation pool: ``submit_many`` splits a
    batch into that many contiguous shards and runs each shard's batched
    forward on its own pool worker, so a slow annotation batch never
    serializes behind a single worker and the engine's per-lane commit
    drain can consume early shards while later ones are still in flight.
    ``workers="auto"`` hands the width to the engine's queue-depth
    autoscaler (core/batched.py).

    ``backend`` picks the pool: ``"thread"`` (default — jitted dispatch
    releases the GIL while the device executes, so threads already
    overlap) or ``"process"`` for GIL-bound annotators: a spawn-context
    ``ProcessPoolExecutor`` whose children get the host-gathered params
    at fork-free init and jit their own forward (spawn, never fork —
    XLA's runtime threads don't survive forking).  The executor is
    sized to ``max(workers, max_workers)`` so autoscaling up never
    needs a pool rebuild; a broken process pool (a child died) is
    detected and rebuilt on the next submit.  The process backend is
    refused off the CPU backend: an accelerator belongs to one process,
    and the parent already holds it.
    """

    params: dict
    spec: TinyTFSpec
    name: str = "model-expert"
    cost: float = 1.0e6
    workers: Union[int, str] = 1
    backend: str = "thread"
    max_workers: Optional[int] = None
    _executor: Optional[ThreadPoolExecutor] = field(     # guarded-by: _lock
        default=None, init=False, repr=False, compare=False)
    _lock: threading.RLock = field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self):
        spec = self.spec
        if self.backend not in ("thread", "process"):
            raise ValueError(f"backend must be 'thread' or 'process', "
                             f"got {self.backend!r}")
        if self.backend == "process" and jax.default_backend() != "cpu":
            raise RuntimeError(
                f"ModelExpert(backend='process') on a "
                f"{jax.default_backend()!r} backend: an accelerator "
                f"belongs to one process, and this one already holds it, "
                f"so spawned annotator children would fail or hang "
                f"reaching the device. Use backend='thread' (one process "
                f"per chip); the process pool is for CPU-only runs.")
        self.auto_workers = self.workers == "auto"
        self.workers = 1 if self.auto_workers else max(int(self.workers), 1)
        self._lock = threading.RLock()
        self._predict = jax.jit(_san.trace_probe(
            "expert.predict", lambda p, ids: tinytf_predict(p, ids, spec)))

    def label(self, idx: int, doc: np.ndarray) -> int:
        """Annotate one stream item with a single model forward."""
        ids = hash_ids(doc, self.spec.vocab, self.spec.max_len)[None]
        probs = self._predict(self.params, jnp.asarray(ids))
        return int(jnp.argmax(probs[0]))

    def label_batch(self, idxs, docs) -> np.ndarray:
        """One batched forward for a tick's whole deferred subset.

        Its host spans (``ocl.featurize``, ``ocl.wait``, level
        ``expert``) run on the calling thread — a pool worker under
        ``submit``/``submit_many`` — and carry no tick: the engine's
        ``ocl.expert`` span around the same annotation carries it."""
        if len(docs) == 0:
            return np.zeros((0,), np.int32)
        with TraceAnnotation("ocl.featurize", level="expert",
                             rows=len(docs)):
            ids = np.stack([hash_ids(d, self.spec.vocab, self.spec.max_len)
                            for d in docs])
        probs = self._predict(self.params, jnp.asarray(ids))
        with TraceAnnotation("ocl.wait", level="expert"):
            return np.asarray(jnp.argmax(probs, axis=-1), np.int32)

    # -- async interface: shard forwards run on pool threads, so the
    #    expert's host+device time overlaps the engine's next-tick
    #    student compute (jitted dispatch releases the GIL while the
    #    device executes; shard layout is deterministic — shard_bounds)
    def _pool_width(self) -> int:
        return max(self.workers,
                   self.max_workers if self.max_workers else 1)

    def _pool(self):
        with self._lock:
            ex = self._executor
            if ex is not None and getattr(ex, "_broken", False):
                # a dead child poisons the whole ProcessPoolExecutor;
                # rebuild so requeued shards land on fresh workers
                ex.shutdown(wait=False, cancel_futures=True)
                ex = self._executor = None
            if ex is None:
                if self.backend == "process":
                    import multiprocessing as mp
                    host_params = jax.device_get(self.params)
                    self._executor = ProcessPoolExecutor(
                        max_workers=self._pool_width(),
                        mp_context=mp.get_context("spawn"),
                        initializer=_process_worker_init,
                        initargs=(host_params, self.spec))
                else:
                    self._executor = ThreadPoolExecutor(
                        max_workers=self._pool_width(),
                        thread_name_prefix=self.name)
            return self._executor

    def _task(self):
        # process children can't pickle the jitted bound method; they
        # run the module-level twin against their initializer state
        return (_process_label_batch if self.backend == "process"
                else self.label_batch)

    def submit(self, idxs, docs) -> ExpertTicket:
        """Enqueue a batch annotation as ONE pool request (kept for the
        per-tick commit path, where only whole-batch completion
        matters)."""
        return ExpertTicket(
            future=self._pool().submit(self._task(), list(idxs),
                                       list(docs)))

    def submit_many(self, idxs, docs) -> ExpertTicket:
        """Enqueue a batch sharded over the worker pool; the returned
        ticket completes per item as each shard's forward lands."""
        idxs = list(idxs)
        docs = list(docs)
        pool = self._pool()
        task = self._task()
        shards = [
            (lo, hi, pool.submit(task, idxs[lo:hi], docs[lo:hi]))
            for lo, hi in shard_bounds(len(idxs), self.workers)]
        return ExpertTicket(shards=shards)

    def poll(self, ticket: ExpertTicket,
             block: bool = True) -> Optional[np.ndarray]:
        """Labels when ready, else None (non-blocking poll)."""
        return poll_ticket(ticket, block)

    def poll_partial(self, ticket: ExpertTicket):
        """Non-blocking partial poll: (ready_mask, labels-with--1)."""
        return poll_ticket_partial(ticket)

    def close(self) -> None:
        """Reap the pool threads (long-lived processes that cycle
        through many experts should call this; idempotent)."""
        with self._lock:
            if self._executor is not None:
                self._executor.shutdown(wait=True)
                self._executor = None

    def __del__(self):  # best-effort: don't leak the workers at GC
        try:
            self.close()
        except Exception:
            pass


def train_model_expert(stream: Stream, n_classes: int,
                       d_model: int = 256, n_layers: int = 4,
                       epochs: int = 3, batch: int = 32,
                       lr: float = 1e-3, seed: int = 0,
                       max_samples: Optional[int] = None,
                       cost: float = 1.0e6,
                       workers: Union[int, str] = 1,
                       backend: str = "thread") -> ModelExpert:
    """Train the stand-in LLM on ground truth (offline, before serving)."""
    spec = TinyTFSpec(d_model=d_model, n_layers=n_layers, d_ff=4 * d_model,
                      n_classes=n_classes)
    params = tinytf_init(jax.random.PRNGKey(seed), spec)
    opt = adam(lr)
    state = opt.init(params)
    n = len(stream) if max_samples is None else min(max_samples, len(stream))
    ids = np.stack([hash_ids(stream.docs[i], spec.vocab, spec.max_len)
                    for i in range(n)])
    labels = stream.labels[:n]

    @jax.jit
    def step(params, state, xb, yb):
        loss, grads = jax.value_and_grad(
            lambda p: tinytf_loss(p, xb, yb, spec))(params)
        params, state = opt.step(params, grads, state)
        return params, state, loss

    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(n)
        for s in range(0, n - batch + 1, batch):
            sel = order[s:s + batch]
            params, state, _ = step(params, state,
                                    jnp.asarray(ids[sel]),
                                    jnp.asarray(labels[sel]))
    return ModelExpert(params=params, spec=spec, cost=cost, workers=workers,
                       backend=backend)
