"""Micro-batched serving layer: shape buckets + request queue (DESIGN.md §8).

The engine answers a batch in one jit'd call (``engine.run_query_batch``,
lane-masked early exit), but live traffic arrives one query at a time with
ragged pattern counts. This module is the glue between the two:

* **Shape buckets** — every distinct ``(Q, T)`` shape is a separate XLA
  compilation. Requests' ``(T,)`` pattern vectors are padded up to a small
  fixed set of T buckets, and batches are padded up to a small set of Q
  buckets, so steady-state traffic reuses a handful of jit specializations
  instead of compiling per shape. Pad lanes are all-``PAD_KEY`` queries;
  the executor proves them done on their first trip, and pad patterns are
  inactive streams — both are unpadded away before results are returned.

* **Micro-batching** — ``MicroBatcher`` queues concurrent requests and
  flushes a batch when it reaches ``max_batch`` or the oldest request has
  waited ``max_wait_s``, the standard throughput/latency dial of serving
  stacks. ``BatchExecutor`` is the synchronous core (give it a list of
  queries, get per-request results); the queue layer sits on top and is
  optional — offline consumers (benchmarks, bulk evaluation) call the
  executor directly.

* **Continuous refill** — with ``BatchingConfig.refill`` the flush group
  becomes the device-resident admission queue of ONE streaming call
  (``engine.run_query_stream_with_masks``): ``lanes`` lanes run in
  lockstep and a finished lane is spliced with the next queued query
  instead of freezing until the batch tail, so up to ``refill_depth``
  queries amortize a single dispatch and lockstep waste shrinks to the
  end-of-queue drain. ``pipeline`` double-buffers the offline path: the
  host plans group i+1 while the device executes group i.

Both execution paths run the engine's ONE unified executor loop
(``engine.execute_queue`` → ``engine._execute_refill``): the fixed-batch
call is its lanes = Q degenerate configuration and the refill call its
general lanes < M configuration, so "which executor" is purely a
(queue depth, lanes) knob setting here — there is no second loop body.

Correctness contract: per-request results are element-wise identical to
``engine.run_query`` on the unpadded query (tests/test_serving.py,
tests/test_refill.py, tests/test_executor_equiv.py).
"""
from __future__ import annotations

import contextvars
import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.types import EngineConfig, PAD_KEY

# The request numbers (``MicroBatcher.submit``'s ``r<n>``) of the group
# being served, for the executor's host spans; empty off the MicroBatcher.
_REQUESTS: contextvars.ContextVar[str] = contextvars.ContextVar(
    "requests", default="")


def _span(name: str, requests: str | None = None,
          **meta) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler's trace, on the device trace's clock,
    tagged with ``requests`` (default: those being served). Costs about
    a microsecond when no trace is being taken. Metadata values are
    integers or strings that do not start with a digit: the profiler
    reads "1,2" as the number 1."""
    if requests is None:
        requests = _REQUESTS.get()
    return jax.profiler.TraceAnnotation(name, requests=requests, **meta)


def _requests(items) -> str:
    """``"r3 r4"`` for MicroBatcher queue items (query, future, number)."""
    return " ".join(f"r{n}" for _, _, n in items)


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket ≥ n (buckets sorted ascending)."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


def default_t_buckets(t_max: int) -> tuple[int, ...]:
    """Powers of two from 2 up to a cover of t_max.

    The cover itself is a power of two (never t_max verbatim): with
    ``t_buckets=None`` every observed T must round UP to a shared bucket,
    or each distinct pattern count would become its own jit specialization
    — exactly the per-shape compile churn buckets exist to prevent.
    """
    out, b = [], 2
    while b < max(t_max, 2):
        out.append(b)
        b *= 2
    out.append(b)
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class BatchingConfig:
    """Serving-layer knobs (engine knobs live in EngineConfig)."""

    max_batch: int = 16            # flush threshold / largest micro-batch
    max_wait_s: float = 0.002      # oldest request's max queue wait
    # Query-count pads: a flushed group of n requests runs at the smallest
    # bucket ≥ n. Must cover max_batch.
    q_buckets: tuple[int, ...] = (1, 4, 16, 64)
    # Pattern-count pads; None derives powers-of-two from observed queries.
    t_buckets: tuple[int, ...] | None = None
    # --- continuous-refill streaming executor (DESIGN.md §8) ---
    # refill=True routes execution through engine.run_query_stream_with_
    # _masks: a whole admission queue of up to ``refill_depth`` queries is
    # shipped to the device, and a lane whose HRJN bound closes is spliced
    # with the next queued query instead of freezing until the batch tail.
    refill: bool = False
    # Device lanes for the streaming executor (None → max_batch). Part of
    # the jit key: one specialization per (depth bucket, t bucket, lanes).
    lanes: int | None = None
    # Queue entries per streaming call; the refill analogue of max_batch.
    refill_depth: int = 64
    # Double-buffered plan/execute: BatchExecutor.run plans chunk i+1 on a
    # host thread while the device executes chunk i.
    pipeline: bool = False

    def __post_init__(self):
        # Validate at construction time with real exceptions (asserts
        # vanish under `python -O`, and a bad knob that slips through
        # here only surfaces as a shape error deep inside jit).
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_batch > max(self.q_buckets):
            raise ValueError(
                f"q_buckets {self.q_buckets} must cover max_batch "
                f"{self.max_batch}")
        if self.lanes is not None and self.lanes < 1:
            raise ValueError(f"lanes must be >= 1 (or None), got {self.lanes}")
        if self.refill_depth < 1:
            raise ValueError(
                f"refill_depth must be >= 1, got {self.refill_depth}")
        if self.refill and self.refill_depth < self.max_batch:
            raise ValueError(
                "refill_depth must cover max_batch (MicroBatcher flush "
                f"groups are admitted whole): {self.refill_depth} < "
                f"{self.max_batch}")


@dataclasses.dataclass(frozen=True)
class ServedResult:
    """Per-request view of one lane of a batched EngineResult."""

    keys: np.ndarray       # (k,) int32
    scores: np.ndarray     # (k,) f32
    n_pulled: int
    n_answers: int
    n_iters: int
    n_wasted: int          # lockstep trips this lane sat frozen
    relax_mask: np.ndarray  # (T, R) for the request's true T
    batch_size: int        # real requests in the micro-batch served with


@dataclasses.dataclass
class BatchStats:
    """One record per executed micro-batch (benchmark/report fodder)."""

    n_requests: int        # real requests
    q_bucket: int
    t_bucket: int
    # Execute-phase wall time, dispatch to ready. Where the call plans
    # for itself (masks=None, the served path) it holds the planner's
    # device time too: planning is dispatched without waiting.
    exec_s: float
    n_iters: int           # batch lockstep trips (max over lanes)
    useful_iters: int      # sum over real lanes of per-lane n_iters
    wasted_iters: int      # sum over real lanes of per-lane n_wasted
    # Plan-phase wall time attributed to this batch: 0.0, since no call
    # waits for a plan of its own (plan_group's waits are totalled in
    # BatchExecutor.plan_total_s).
    plan_s: float = 0.0


class BatchExecutor:
    """Synchronous bucketed batch execution against one store.

    Pads queries into shape buckets, runs ``engine.run_query_batch`` per
    bucket, unpads per-request results. The jit cache is keyed by the
    bucketed ``(Q, T)`` shapes, so ``warmup()`` can pre-compile the whole
    bucket grid before traffic hits.
    """

    def __init__(self, store, relax, cfg: EngineConfig, mode: str = "specqp",
                 bcfg: BatchingConfig = BatchingConfig()):
        self.store = store
        self.relax = relax
        self.cfg = cfg
        self.mode = mode
        self.bcfg = bcfg
        # Recent-batch records, bounded so a long-lived server does not
        # grow without bound; aggregate metrics use the running totals
        # below, which cover every batch ever served since reset_stats().
        # All of them are mutated from more than one thread — the
        # pipelined planner thread bumps plan_total_s while the main
        # thread records the previous group, and a MicroBatcher worker
        # records batches while callers poll wasted_fraction() — so every
        # access goes through _lock (speclint LD001 enforces this).
        self._lock = threading.Lock()
        self.stats: list[BatchStats] = []
        self.stats_cap = 4096
        self._plan_total_s = 0.0  # plan-phase wall time (offline pipeline)
        self._useful_total = 0
        self._wasted_total = 0
        # Host-side copies for the work scheduler (batch composition).
        self._lengths = np.asarray(store.lengths)
        self._rel_ids = np.asarray(relax.ids)

    def reset_stats(self) -> None:
        with self._lock:
            self.stats.clear()
            self._plan_total_s = 0.0
            self._useful_total = 0
            self._wasted_total = 0

    @property
    def plan_total_s(self) -> float:
        """Plan-phase wall time since reset_stats() (thread-safe read)."""
        with self._lock:
            return self._plan_total_s

    def _t_bucket(self, t: int) -> int:
        if self.bcfg.t_buckets is not None:
            return bucket_for(t, self.bcfg.t_buckets)
        return bucket_for(t, default_t_buckets(max(t, 2)))

    def _lanes_n(self) -> int:
        """Device lanes for the streaming executor."""
        return self.bcfg.lanes or self.bcfg.max_batch

    def _m_buckets(self) -> tuple[int, ...]:
        """Queue-depth pads for the streaming executor: the q buckets that
        fit, topped by refill_depth itself (pad entries are all-PAD
        queries, one executor trip each — depth padding is cheap)."""
        return tuple(sorted({b for b in self.bcfg.q_buckets
                             if b <= self.bcfg.refill_depth}
                            | {self.bcfg.refill_depth}))

    def _m_bucket(self, n: int) -> int:
        return bucket_for(n, self._m_buckets())

    @staticmethod
    def _true_t(q: np.ndarray) -> int:
        q = np.asarray(q)
        return int((q != int(PAD_KEY)).sum())

    def _pad_group(self, group: list[np.ndarray], t_b: int,
                   q_b: int) -> jax.Array:
        with _span("executor.pad"):
            batch = np.full((q_b, t_b), int(PAD_KEY), np.int32)
            for i, q in enumerate(group):
                q = np.asarray(q, np.int32)
                q = q[q != int(PAD_KEY)]
                batch[i, :len(q)] = q
            return jnp.asarray(batch)

    def warmup(self, t_buckets: tuple[int, ...] | None = None) -> int:
        """Compile every (q_bucket, t_bucket) specialization; returns count.

        The dummy batches are all-pad queries — one executor trip each, so
        warmup cost is compile-dominated, not execute-dominated. Both phases
        (plan, execute-with-masks) are compiled per shape.
        """
        t_buckets = t_buckets or self.bcfg.t_buckets
        if not t_buckets:
            raise ValueError("warmup needs explicit or configured t_buckets")
        q_cover = bucket_for(self.bcfg.max_batch, self.bcfg.q_buckets)
        n = 0
        for t_b in t_buckets:
            for q_b in self.bcfg.q_buckets:
                if q_b > q_cover:
                    continue
                dummy = jnp.full((q_b, t_b), PAD_KEY, jnp.int32)
                masks = engine.plan_query_batch(
                    self.store, self.relax, dummy, self.cfg, self.mode)
                # With refill on, the fixed-batch configuration is
                # unreachable (run_batch redirects to run_stream) — warm
                # only the plan shapes, which plan chunking still uses.
                if not self.bcfg.refill:
                    jax.block_until_ready(
                        engine.run_query_batch_with_masks(
                            self.store, self.relax, dummy, masks,
                            self.cfg).scores)
                n += 1
            if not self.bcfg.refill:
                continue
            # Streaming specializations: (depth bucket, t bucket, lanes).
            for m_b in self._m_buckets():
                dummy = jnp.full((m_b, t_b), PAD_KEY, jnp.int32)
                masks = engine.plan_query_batch(
                    self.store, self.relax, dummy, self.cfg, self.mode)
                jax.block_until_ready(engine.run_query_stream_with_masks(
                    self.store, self.relax, dummy, masks, self.cfg,
                    min(self._lanes_n(), m_b)).scores)
                n += 1
        return n

    def plan_group(self, group: list[np.ndarray], q_b: int | None = None
                   ) -> tuple[list[np.ndarray], float]:
        """Plan phase: (T, R) masks per request (batched, bucket shapes).

        ``q_b`` overrides the batch-size pad (the refill path plans at its
        queue-depth buckets so plan and execute share jit shapes)."""
        t_b = self._t_bucket(max(self._true_t(q) for q in group))
        if q_b is None:
            q_b = bucket_for(len(group), self.bcfg.q_buckets)
        batch = self._pad_group(group, t_b, q_b)
        t0 = time.perf_counter()
        masks = engine.plan_query_batch(self.store, self.relax, batch,
                                        self.cfg, self.mode)
        masks = np.asarray(masks)
        dt = time.perf_counter() - t0
        # plan_group runs on the planner thread when pipelining — the
        # bare `+=` here used to race _finish_batch on the main thread.
        with self._lock:
            self._plan_total_s += dt
        return [masks[i] for i in range(len(group))], dt

    def planned_work(self, q: np.ndarray, mask: np.ndarray) -> int:
        """Pullable items under the plan: lengths of the enabled sources."""
        t = np.asarray(q)
        t = t[t != int(PAD_KEY)]
        rel = self._rel_ids[t]                          # (T, R)
        on = mask[:len(t)] & (rel >= 0)
        return int(self._lengths[t].sum() +
                   self._lengths[np.where(rel >= 0, rel, 0)][on].sum())

    def _mask_batch(self, masks: list[np.ndarray], q_b: int,
                    t_b: int) -> jax.Array:
        R = self._rel_ids.shape[1]
        mask_b = np.zeros((q_b, t_b, R), bool)
        for i, m in enumerate(masks):
            # Rows past a query's true T are all-False padding, so
            # trimming to this batch's t_b is lossless.
            mask_b[i, :min(m.shape[0], t_b)] = m[:t_b]
        return jnp.asarray(mask_b)

    def _plan(self, batch: jax.Array, masks: list[np.ndarray] | None,
              q_b: int, t_b: int) -> jax.Array:
        """The batch's (Q, T, R) plans: padded from ``plan_group``'s
        ``masks``, or, when None (the served path), dispatched here
        without waiting, so the planner's device time lands in the
        execute call's wait."""
        if masks is not None:
            return self._mask_batch(masks, q_b, t_b)
        with _span("planner.dispatch"):
            return engine.plan_query_batch(self.store, self.relax, batch,
                                           self.cfg, self.mode)

    def _finish_batch(self, res, group: list[np.ndarray], q_b: int,
                      t_b: int, dt: float, lanes: int | None = None
                      ) -> list[ServedResult]:
        """Unpad per-request results + record stats (both exec paths).

        ``lanes`` is None for a fixed batch, whose lockstep trips are the
        slowest lane's and whose waste sums the real lanes: a pad lane's
        frozen trips are padding artifact. A streaming call's trips are
        its lane-trips (useful + idle, pad entries included) spread over
        its ``lanes``, display-only, and its waste sums ALL queue
        entries, because an idle lane's drain trips are attributed to
        the last entry it served — which can be a pad entry when the
        queue was padded to its depth bucket."""
        with _span("executor.unpad"):
            keys = np.asarray(res.keys)
            scores = np.asarray(res.scores)
            mask = np.asarray(res.relax_mask)
            n_pulled = np.asarray(res.n_pulled)
            n_answers = np.asarray(res.n_answers)
            n_iters = np.asarray(res.n_iters)
            n_wasted = np.asarray(res.n_wasted)
            out = [ServedResult(
                keys=keys[i], scores=scores[i],
                n_pulled=int(n_pulled[i]), n_answers=int(n_answers[i]),
                n_iters=int(n_iters[i]), n_wasted=int(n_wasted[i]),
                relax_mask=mask[i, :self._true_t(q)],
                batch_size=len(group)) for i, q in enumerate(group)]
        useful = int(n_iters[:len(group)].sum())
        if lanes is None:
            trips = int(n_iters.max())
            wasted = int(n_wasted[:len(group)].sum())
        else:
            wasted = int(n_wasted.sum())
            trips = -(-(int(n_iters.sum()) + wasted) // lanes)
        with self._lock:
            self._useful_total += useful
            self._wasted_total += wasted
            self.stats.append(BatchStats(
                n_requests=len(group), q_bucket=q_b, t_bucket=t_b,
                exec_s=dt, n_iters=trips, useful_iters=useful,
                wasted_iters=wasted))
            if len(self.stats) > self.stats_cap:
                del self.stats[:-self.stats_cap]
        return out

    def run_batch(self, group: list[np.ndarray],
                  masks: list[np.ndarray] | None = None
                  ) -> list[ServedResult]:
        """Serve one micro-batch of same-T-bucket queries (≤ max_batch).

        ``masks`` — precomputed plans from ``plan_group`` (the offline
        scheduler plans ahead to compose batches by planned work); when
        None, the plan phase runs here on the same padded batch. Either
        way results are identical to per-query ``run_query``. With
        ``BatchingConfig.refill`` the group is served by the streaming
        executor instead (``run_stream``) — same contract, lower waste.
        """
        if self.bcfg.refill:
            return self.run_stream(group, masks)
        if not 0 < len(group) <= self.bcfg.max_batch:
            raise ValueError(
                f"group size {len(group)} not in [1, {self.bcfg.max_batch}]")
        t_b = self._t_bucket(max(self._true_t(q) for q in group))
        q_b = bucket_for(len(group), self.bcfg.q_buckets)
        with _span("executor.call", depth=q_b, t_bucket=t_b, n=len(group)):
            batch = self._pad_group(group, t_b, q_b)
            mask_b = self._plan(batch, masks, q_b, t_b)
            t0 = time.perf_counter()
            with _span("executor.dispatch"):
                res = engine.run_query_batch_with_masks(
                    self.store, self.relax, batch, mask_b, self.cfg)
            with _span("executor.wait"):
                jax.block_until_ready(res.scores)
            dt = time.perf_counter() - t0
            return self._finish_batch(res, group, q_b, t_b, dt)

    def run_stream(self, group: list[np.ndarray],
                   masks: list[np.ndarray] | None = None
                   ) -> list[ServedResult]:
        """Serve one admission queue (≤ refill_depth queries) through the
        continuous-refill streaming executor.

        The group is the device-resident admission queue of ONE
        ``engine.run_query_stream_with_masks`` call: ``lanes`` lanes run
        in lockstep and each finished lane is immediately spliced with the
        next queued query. Per-request results are element-wise identical
        to ``run_query``; the batch-tail freeze of ``run_batch`` shrinks
        to the end-of-queue drain.
        """
        if not 0 < len(group) <= self.bcfg.refill_depth:
            raise ValueError(
                f"queue size {len(group)} not in "
                f"[1, {self.bcfg.refill_depth}]")
        t_b = self._t_bucket(max(self._true_t(q) for q in group))
        m_b = self._m_bucket(len(group))
        # A lane beyond the queue depth would idle from trip one yet
        # still pay the vmapped step every trip — cap lanes at the padded
        # depth (static per jit shape, so this costs no extra compiles
        # beyond the (m_b, t_b) grid warmup already covers).
        lanes = min(self._lanes_n(), m_b)
        with _span("executor.call", depth=m_b, t_bucket=t_b, n=len(group)):
            batch = self._pad_group(group, t_b, m_b)
            mask_b = self._plan(batch, masks, m_b, t_b)
            t0 = time.perf_counter()
            with _span("executor.dispatch"):
                res = engine.run_query_stream_with_masks(
                    self.store, self.relax, batch, mask_b, self.cfg, lanes)
            with _span("executor.wait"):
                jax.block_until_ready(res.scores)
            dt = time.perf_counter() - t0
            return self._finish_batch(res, group, m_b, t_b, dt, lanes)

    def _exec_cap(self) -> int:
        return (self.bcfg.refill_depth if self.bcfg.refill
                else self.bcfg.max_batch)

    def run(self, queries: list[np.ndarray]) -> list[ServedResult]:
        """Serve a request list offline: plan → schedule → execute.

        Per T bucket: the plan phase runs batched over arrival order (the
        planner vectorizes across lanes and has no lockstep loop, so batch
        composition is irrelevant there); then execution groups are
        composed by *planned work* — the pullable source lengths each plan
        enabled. For the fixed-batch path, ascending order packs
        similar-cost lanes into one lockstep loop (a heavy query mixed
        into a light batch makes every light lane burn frozen trips). For
        the refill path the admission queue absorbs skew by construction,
        and descending order (longest processing time first) shrinks the
        end-of-queue drain instead. With ``BatchingConfig.pipeline`` the
        plan phase of group i+1 overlaps the execute phase of group i
        (``_run_pipelined``). Order of results matches ``queries``.
        """
        if self.bcfg.pipeline:
            return self._run_pipelined(queries)
        by_bucket: dict[int, list[int]] = {}
        for i, q in enumerate(queries):
            by_bucket.setdefault(self._t_bucket(self._true_t(q)), []).append(i)
        out: list[ServedResult | None] = [None] * len(queries)
        serve = self.run_stream if self.bcfg.refill else self.run_batch
        exec_cap = self._exec_cap()
        for _, idxs in sorted(by_bucket.items()):
            masks: dict[int, np.ndarray] = {}
            # Plan at the exec path's own shape family: depth buckets for
            # refill (fewer, bigger dispatches — warmup compiled them),
            # q buckets for fixed batches.
            chunk_cap = (self.bcfg.refill_depth if self.bcfg.refill
                         else bucket_for(self.bcfg.max_batch,
                                         self.bcfg.q_buckets))
            for c in range(0, len(idxs), chunk_cap):
                chunk = idxs[c:c + chunk_cap]
                q_b = (self._m_bucket(len(chunk)) if self.bcfg.refill
                       else None)
                ms, _ = self.plan_group([queries[j] for j in chunk], q_b)
                masks.update(zip(chunk, ms))
            idxs = sorted(idxs, key=lambda j: self.planned_work(
                queries[j], masks[j]), reverse=self.bcfg.refill)
            for c in range(0, len(idxs), exec_cap):
                chunk = idxs[c:c + exec_cap]
                rs = serve([queries[j] for j in chunk],
                           masks=[masks[j] for j in chunk])
                for j, r in zip(chunk, rs):
                    out[j] = r
        return out  # type: ignore[return-value]

    def _run_pipelined(self, queries: list[np.ndarray]
                       ) -> list[ServedResult]:
        """Double-buffered plan/execute: the host plans execution group
        i+1 on a worker thread while the device executes group i.

        Groups follow arrival order — the planned-work sort of ``run``
        needs every plan before the first execute, which is exactly the
        barrier the pipeline removes (the refill executor absorbs the
        skew the sort existed to dodge). jax dispatch releases the GIL
        during device compute, so the overlap is real wall-clock overlap
        wherever the planner and the executor do not contend for cores.
        """
        by_bucket: dict[int, list[int]] = {}
        for i, q in enumerate(queries):
            by_bucket.setdefault(self._t_bucket(self._true_t(q)), []).append(i)
        out: list[ServedResult | None] = [None] * len(queries)
        serve = self.run_stream if self.bcfg.refill else self.run_batch
        exec_cap = self._exec_cap()
        chunks = []
        for _, idxs in sorted(by_bucket.items()):
            chunks += [idxs[c:c + exec_cap]
                       for c in range(0, len(idxs), exec_cap)]

        def plan_for(chunk):
            group = [queries[j] for j in chunk]
            q_b = (self._m_bucket(len(chunk)) if self.bcfg.refill
                   else bucket_for(len(chunk), self.bcfg.q_buckets))
            return self.plan_group(group, q_b)[0]

        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="planner") as pool:
            fut = pool.submit(plan_for, chunks[0]) if chunks else None
            for c, chunk in enumerate(chunks):
                ms = fut.result()
                if c + 1 < len(chunks):
                    fut = pool.submit(plan_for, chunks[c + 1])
                rs = serve([queries[j] for j in chunk], masks=ms)
                for j, r in zip(chunk, rs):
                    out[j] = r
        return out  # type: ignore[return-value]

    def wasted_fraction(self) -> float:
        """Fraction of real-lane lockstep trips spent frozen, since the
        last ``reset_stats()`` (running totals — O(1), unbounded window)."""
        with self._lock:
            return self._wasted_total / max(
                self._useful_total + self._wasted_total, 1)


class MicroBatcher:
    """Threaded request queue in front of a BatchExecutor.

    ``submit`` returns a Future resolving to a ServedResult. A worker
    thread flushes a micro-batch when ``max_batch`` requests are queued or
    the oldest has waited ``max_wait_s``. Flushed requests are grouped by
    T bucket (one executor call per group) so shape specializations are
    reused. Use as a context manager, or call ``close()``.

    Every request gets a running number, ``r<n>`` in the metadata of the
    host spans that serve it: ``frontend.collect`` (first request taken
    to flush), ``frontend.flush`` and the executor's spans inside it.
    """

    _STOP = object()

    def __init__(self, executor: BatchExecutor):
        self.executor = executor
        self._q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._closed = False
        self._numbers = itertools.count(1)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit(self, query: np.ndarray) -> Future:
        """Enqueue one request. After ``close()`` the returned future
        fails immediately with RuntimeError instead of hanging — a
        request can never be enqueued behind the stop sentinel."""
        fut: Future = Future()
        with self._lock:
            if self._closed:
                fut.set_exception(RuntimeError(
                    "MicroBatcher is closed; request rejected"))
                return fut
            self._q.put((np.asarray(query, np.int32), fut,
                         next(self._numbers)))
        return fut

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def close(self):
        """Stop accepting requests, drain the queue, join the worker.

        Every future submitted before close() resolves (with a result or
        the error its batch raised) before this returns; submits that
        race with close() either make it in before the sentinel or fail
        fast in ``submit``. Idempotent.
        """
        with self._lock:
            already = self._closed
            self._closed = True
            if not already:
                self._q.put(self._STOP)
        if self._thread.is_alive():
            self._thread.join()

    def _loop(self):
        bcfg = self.executor.bcfg
        while True:
            item = self._q.get()
            if item is self._STOP:
                self._drain_and_exit([])
                return
            pending, stop = [item], False
            with jax.profiler.TraceAnnotation("frontend.collect") as span:
                deadline = time.perf_counter() + bcfg.max_wait_s
                while len(pending) < bcfg.max_batch:
                    left = deadline - time.perf_counter()
                    if left <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=left)
                    except queue.Empty:
                        break
                    if nxt is self._STOP:
                        stop = True
                        break
                    pending.append(nxt)
                span.set_metadata(requests=_requests(pending))
            if stop:
                self._drain_and_exit(pending)
                return
            self._flush(pending)

    def _drain_and_exit(self, pending):
        """Serve everything still queued at shutdown so no future is
        stranded (regression: requests behind the stop sentinel used to
        hang forever)."""
        pending = list(pending)
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not self._STOP:
                pending.append(item)
        cap = self.executor.bcfg.max_batch
        for c in range(0, len(pending), cap):
            self._flush(pending[c:c + cap])

    def _flush(self, pending):
        """Serve one flush group. Never raises: any error — bucketing a
        malformed query as much as an executor failure — is routed to the
        affected Futures so the worker thread survives and later submits
        still resolve."""
        if not pending:
            return
        with _span("frontend.flush", _requests(pending)):
            by_bucket: dict[int, list[tuple[np.ndarray, Future, int]]] = {}
            for q, fut, n in pending:
                try:
                    t_b = self.executor._t_bucket(self.executor._true_t(q))
                except Exception as e:  # noqa: BLE001 — fail the request only
                    fut.set_exception(e)
                    continue
                by_bucket.setdefault(t_b, []).append((q, fut, n))
            for _, items in sorted(by_bucket.items()):
                token = _REQUESTS.set(_requests(items))
                try:
                    results = self.executor.run_batch(
                        [q for q, _, _ in items])
                    for (_, fut, _), r in zip(items, results):
                        fut.set_result(r)
                except Exception as e:  # noqa: BLE001 — fail the batch, not the server
                    for _, fut, _ in items:
                        if not fut.done():
                            fut.set_exception(e)
                finally:
                    _REQUESTS.reset(token)
