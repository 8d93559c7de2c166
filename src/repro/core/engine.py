"""Query engines: TriniT (non-speculative baseline), Spec-QP, and oracles.

One mask-parameterized executor serves every engine (DESIGN.md §2): the plan
is a ``(T, R)`` boolean — one bit per (pattern, relaxation) pair — saying
which relaxation source lists join the merge. TriniT is the all-True plan;
Spec-QP uses PLANGEN's per-relaxation speculation; ``specqp_pattern`` is the
paper's coarser per-pattern speculation (``mask.any(axis=1)`` broadcast),
kept as an ablation baseline. The executor is an n-ary bound-driven rank
join over blockwise incremental merges, carried entirely through
``lax.while_loop`` so the whole query (planning included) jits and vmaps.

There is exactly ONE executor loop (``_execute_refill``, reached via
``execute_queue``): single-query, fixed-batch, and continuous-refill
serving are degenerate configurations of its (queue depth M, lanes)
knobs — see the ``_execute_refill`` docstring for the table. Answer
equality across configurations is machine-checked by
tests/test_executor_equiv.py against the ``naive_full_scan`` oracle.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import (TripleStore, RelaxTable, EngineResult,
                              EngineConfig, PAD_KEY, NEG_INF)
from repro.core import operators as ops
from repro.core import plangen
from repro.core import scopes


class _LoopState(NamedTuple):
    cursors: jax.Array      # (T, R1)
    seen_keys: jax.Array    # (T, N)
    seen_scores: jax.Array  # (T, N)
    seen_cnt: jax.Array     # (T,)
    top_keys: jax.Array     # (k,)
    top_scores: jax.Array   # (k,)
    n_pulled: jax.Array
    n_answers: jax.Array
    n_iters: jax.Array
    n_wasted: jax.Array     # lockstep trips spent frozen (batch exec only)
    done: jax.Array


def _seen_size(R1: int, L: int, cfg: EngineConfig) -> int:
    """Per-stream seen-ring length N (a whole number of B-item blocks)."""
    B = cfg.block
    N = R1 * L + 2 * B
    if cfg.seen_cap:
        N = min(N, max(cfg.seen_cap, 2 * B))
    # The seen buffer is a ring of whole B-item blocks: N must be a multiple
    # of B so wrapped appends overwrite exactly one stale block. A ragged N
    # would split appends across two old blocks, leaving half-overwritten
    # stale fragments probe-able forever (duplicate keys double-count in the
    # lookup contraction).
    return -(-N // B) * B


def _max_iters(T: int, R1: int, L: int, cfg: EngineConfig) -> int:
    return T * (R1 * L // cfg.block + 2)


def _init_state(T: int, R1: int, N: int, k: int) -> _LoopState:
    return _LoopState(
        cursors=jnp.zeros((T, R1), jnp.int32),
        seen_keys=jnp.full((T, N), PAD_KEY, jnp.int32),
        seen_scores=jnp.zeros((T, N), jnp.float32),
        seen_cnt=jnp.zeros((T,), jnp.int32),
        top_keys=jnp.full((k,), PAD_KEY, jnp.int32),
        top_scores=jnp.full((k,), NEG_INF, jnp.float32),
        n_pulled=jnp.int32(0), n_answers=jnp.int32(0),
        n_iters=jnp.int32(0), n_wasted=jnp.int32(0), done=jnp.array(False))


def _append_block(seen_keys: jax.Array, seen_scores: jax.Array,
                  seen_cnt: jax.Array, t_star: jax.Array, blk_k: jax.Array,
                  blk_s: jax.Array):
    """Append one B-item block to stream ``t_star``'s seen ring.

    seen_keys/seen_scores: (T, N); seen_cnt: (T,); blk_k/blk_s: (B,).
    Returns the updated (seen_keys, seen_scores, seen_cnt). A NEG_INF
    score (a PAD or dropped slot) is stored as 0.

    N is a multiple of B and every append adds B items (``_seen_size``),
    so the write position ``seen_cnt % N`` is always block-aligned: the
    append replaces exactly one whole block of the (T, N/B, B) view, in
    row ``t_star`` only (once the ring wraps under a ``seen_cap``, the
    oldest block). It is written as one select over that view: T·N
    element selects, with no reduction and no scatter. A
    ``dynamic_update_slice`` would be a scatter under the executor's lane
    vmap, which the CPU backend runs as a scalar loop.
    """
    T, N = seen_keys.shape
    B = blk_k.shape[0]
    rows = jnp.arange(T) == t_star                                  # (T,)
    start_blk = (seen_cnt % jnp.int32(N)) // B                      # (T,)
    hit = rows[:, None] & (jnp.arange(N // B)[None, :]
                           == start_blk[:, None])                   # (T, N/B)
    hit = hit[:, :, None]
    blk_s = jnp.where(blk_s == NEG_INF, 0.0, blk_s)

    def write(ring, blk):
        return jnp.where(hit, blk, ring.reshape(T, N // B, B)).reshape(T, N)

    return (write(seen_keys, blk_k), write(seen_scores, blk_s),
            seen_cnt + jnp.where(rows, B, 0).astype(jnp.int32))


def _step(streams: ops.MergedStreams, st: _LoopState,
          cfg: EngineConfig) -> _LoopState:
    """One pull-join-bound iteration of the rank join for ONE query.

    This is THE loop body: every entry point (single query, fixed batch,
    continuous-refill stream, sharded execution) reaches it through the
    unified executor (``_execute_refill``), which vmaps it across lanes
    and freezes lanes whose HRJN bound has closed.
    """
    T, R1, L = streams.keys.shape
    B = cfg.block
    k = cfg.k
    active = streams.stream_active

    def head_scores(cursors):
        return jax.vmap(ops.merged_head_score)(
            streams.keys, streams.scores, streams.lengths, cursors)

    with jax.named_scope(scopes.PULL):
        nxt = head_scores(st.cursors)                       # (T,)
        nxt = jnp.where(active, nxt, NEG_INF)
        t_star = jnp.argmax(nxt)

        blk_k, blk_s, new_cur_t = ops.pull_block(
            streams.keys[t_star], streams.scores[t_star],
            streams.lengths[t_star], st.cursors[t_star], B)
        n_taken = jnp.sum(blk_k != PAD_KEY)
        blk_k, blk_s = ops.dedup_block(blk_k, blk_s)

    with jax.named_scope(scopes.PROBE):
        # Drop keys this stream already emitted (earlier pull ⇒ ≥ score).
        _, seen_before = ops.lookup_scores(
            st.seen_keys[t_star], st.seen_scores[t_star], blk_k,
            st.seen_cnt[t_star], cfg.use_pallas)
        blk_k = jnp.where(seen_before, PAD_KEY, blk_k)
        blk_s = jnp.where(seen_before, NEG_INF, blk_s)

        # Join the fresh block against every other stream's seen buffer.
        def probe(j):
            return ops.lookup_scores(
                st.seen_keys[j], st.seen_scores[j], blk_k, st.seen_cnt[j],
                cfg.use_pallas)
        s_j, f_j = jax.vmap(probe)(jnp.arange(T))           # (T, B)
        others = active & (jnp.arange(T) != t_star)
        contrib = jnp.sum(jnp.where(others[:, None], s_j, 0.0), axis=0)
        matched = jnp.all(jnp.where(others[:, None], f_j, True), axis=0)
        cand_ok = matched & (blk_k != PAD_KEY)
        cand_scores = jnp.where(cand_ok, blk_s + contrib, NEG_INF)
        cand_keys = jnp.where(cand_ok, blk_k, PAD_KEY)

    with jax.named_scope(scopes.TOPK):
        top_keys, top_scores = ops.topk_insert(
            st.top_keys, st.top_scores, cand_keys, cand_scores, k)

    # Append the block to t*'s seen ring (B slots per pull; it wraps when
    # a seen_cap is configured) as a select of one whole block.
    with jax.named_scope(scopes.APPEND):
        seen_keys, seen_scores, seen_cnt = _append_block(
            st.seen_keys, st.seen_scores, st.seen_cnt, t_star, blk_k, blk_s)
        cursors = jax.vmap(
            lambda t, nc: jnp.where(t == t_star, nc, st.cursors[t]),
            in_axes=(0, None))(jnp.arange(T), new_cur_t)

    # HRJN-style n-ary corner bound for any undiscovered answer.
    with jax.named_scope(scopes.BOUND):
        stream_max = jnp.max(
            jnp.where(streams.lengths > 0, streams.scores[:, :, 0], NEG_INF),
            axis=1)                                             # (T,)
        stream_max = jnp.where(active, stream_max, NEG_INF)
        sum_max = jnp.sum(jnp.where(active, stream_max, 0.0))
        nxt2 = head_scores(cursors)
        nxt2 = jnp.where(active, nxt2, NEG_INF)
        tau = jnp.max(nxt2 + (sum_max - jnp.where(active, stream_max, 0.0)))
        kth = top_scores[k - 1]
        exhausted = jnp.all(nxt2 == NEG_INF)
        done = (kth >= tau) | exhausted

    return _LoopState(
        cursors=cursors, seen_keys=seen_keys, seen_scores=seen_scores,
        seen_cnt=seen_cnt, top_keys=top_keys, top_scores=top_scores,
        n_pulled=st.n_pulled + n_taken.astype(jnp.int32),
        # Counts answer-object *materializations*: under a seen_cap, a
        # key evicted and re-pulled from a later source joins again and
        # is counted again — deliberate, the counter is a work/memory
        # proxy and the re-join is real extra work the cap caused (the
        # top-k buffer itself dedups, so results stay correct).
        n_answers=st.n_answers + jnp.sum(cand_ok).astype(jnp.int32),
        n_iters=st.n_iters + 1, n_wasted=st.n_wasted, done=done)


def _bsel(mask: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    """Per-lane select: broadcast a (Q,) lane mask against (Q, ...) leaves."""
    return jnp.where(mask.reshape(mask.shape + (1,) * (new.ndim - 1)),
                     new, old)


def _splice_lanes(st: _LoopState, streams: ops.MergedStreams,
                  fresh: ops.MergedStreams, refill: jax.Array
                  ) -> tuple[_LoopState, ops.MergedStreams]:
    """Splice freshly admitted queries into finished lanes, in place.

    ``refill`` is a (Q,) lane mask; ``st``/``streams`` carry a leading
    (Q,) lane axis. For masked lanes EVERY field of the lane's _LoopState
    slice is reset to its ``_init_state`` value and the lane's streams are
    replaced by ``fresh``'s slice; unmasked lanes are untouched. Resetting
    the whole slice — cursors, seen rings, seen counter, top-k, every
    counter — is what makes lane recycling leak-proof: the new query can
    never probe a key the previous occupant pulled (or half-evicted), and
    its counters equal a from-scratch ``run_query``. jit-safe by
    construction: the splice is pure ``jnp.where`` selects over fixed-shape
    arrays, so the while-loop carry keeps one static shape regardless of
    which (traced) lanes refill.
    """
    Q, T, R1 = st.cursors.shape
    N = st.seen_keys.shape[2]
    k = st.top_keys.shape[1]
    init = jax.vmap(lambda _: _init_state(T, R1, N, k))(jnp.arange(Q))
    new_st = jax.tree_util.tree_map(
        lambda i, o: _bsel(refill, i, o), init, st)
    new_streams = jax.tree_util.tree_map(
        lambda f, o: _bsel(refill, f, o), fresh, streams)
    return new_st, new_streams


class _RefillCarry(NamedTuple):
    st: _LoopState               # per-lane loop state, leading (lanes,)
    streams: ops.MergedStreams   # per-lane streams, leading (lanes,)
    qidx: jax.Array              # (lanes,) queue entry each lane serves
                                 # (M = never held one)
    next_idx: jax.Array          # () next unadmitted queue entry
    out_keys: jax.Array          # (M, k)
    out_scores: jax.Array        # (M, k)
    out_pulled: jax.Array        # (M,)
    out_answers: jax.Array       # (M,)
    out_iters: jax.Array         # (M,)
    out_wasted: jax.Array        # (M,)
    trips: jax.Array             # () total lockstep trips (safety guard)


def _execute_refill(store: TripleStore, relax: RelaxTable,
                    queue_pids: jax.Array, queue_masks: jax.Array,
                    cfg: EngineConfig, lanes: int) -> _RefillCarry:
    """The one true executor: a continuous-refill lane loop (DESIGN.md §8).

    The whole (M, T) query queue lives on device; ``lanes`` lanes run under
    ONE ``lax.while_loop``. The moment a lane's HRJN bound closes (or its
    iteration budget runs out) its result slice is scattered into the
    output buffers at the lane's queue index, and the next unadmitted
    query is spliced into the freed lane — streams re-gathered, the lane's
    _LoopState slice fully re-initialised (``_splice_lanes``) — instead of
    freezing the lane until the batch tail finishes. Lanes only idle once
    the queue is drained, so the fixed-batch executor's per-batch tail
    barrier becomes a single end-of-stream drain.

    Every public entry point is a degenerate configuration of this loop
    (there is no other loop body; see ``execute_queue``):

      single query  — M = 1, lanes = 1: the lone lane runs one query to
                      completion and the loop exits (out_wasted ≡ 0);
      fixed batch   — lanes = M: every queue entry is admitted up front,
                      ``next_idx`` starts at M, so ``cand >= M`` on every
                      trip and the splice path is statically unreachable —
                      finished lanes freeze exactly like a fixed batch;
      refill stream — lanes < M: the general case described above.

    Per-query results are element-wise identical in every configuration:
    each query runs the same ``_step`` sequence from the same fresh state;
    the lane it happens to occupy is invisible to it. ``out_wasted``
    counts the lockstep trips a lane sat idle after finishing, attributed
    to the LAST query the lane served — in the fixed-batch configuration
    that reproduces the frozen-lane accounting (a lane finished early
    accrues one wasted trip per remaining lockstep trip), and in the
    refill configuration it is the end-of-stream drain (queries served
    mid-stream report 0).
    """
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    M, T = queue_pids.shape
    R1 = relax.ids.shape[1] + 1
    L = store.keys.shape[1]
    N = _seen_size(R1, L, cfg)
    max_iters = _max_iters(T, R1, L, cfg)
    Q = lanes
    trips_cap = M * max_iters + 2

    def admit(i):
        with jax.named_scope(scopes.ADMIT):
            return ops.gather_streams(store, relax, queue_pids[i],
                                      queue_masks[i])

    lane0 = jnp.minimum(jnp.arange(Q), M - 1)
    live0 = jnp.arange(Q) < M
    st0 = jax.vmap(lambda _: _init_state(T, R1, N, cfg.k))(jnp.arange(Q))
    carry0 = _RefillCarry(
        st=st0._replace(done=~live0),
        streams=jax.vmap(admit)(lane0),
        qidx=jnp.where(live0, jnp.arange(Q), M).astype(jnp.int32),
        next_idx=jnp.int32(min(Q, M)),
        out_keys=jnp.full((M, cfg.k), PAD_KEY, jnp.int32),
        out_scores=jnp.full((M, cfg.k), NEG_INF, jnp.float32),
        out_pulled=jnp.zeros((M,), jnp.int32),
        out_answers=jnp.zeros((M,), jnp.int32),
        out_iters=jnp.zeros((M,), jnp.int32),
        out_wasted=jnp.zeros((M,), jnp.int32),
        trips=jnp.int32(0))

    def lane_step(strm, s: _LoopState) -> _LoopState:
        live = ~s.done
        new = _step(strm, s, cfg)
        # Freeze discipline: only result-bearing fields of an idle lane
        # are pinned; its merge state may mutate harmlessly (nothing
        # reads it — a refill replaces it wholesale).
        keep = lambda old, nw: jnp.where(live, nw, old)
        return _LoopState(
            cursors=new.cursors, seen_keys=new.seen_keys,
            seen_scores=new.seen_scores, seen_cnt=new.seen_cnt,
            top_keys=keep(s.top_keys, new.top_keys),
            top_scores=keep(s.top_scores, new.top_scores),
            n_pulled=keep(s.n_pulled, new.n_pulled),
            n_answers=keep(s.n_answers, new.n_answers),
            n_iters=keep(s.n_iters, new.n_iters),
            n_wasted=s.n_wasted,
            done=s.done | new.done | (new.n_iters >= max_iters))

    def body(c: _RefillCarry) -> _RefillCarry:
        live = ~c.st.done
        st = jax.vmap(lane_step)(c.streams, c.st)

        # Emit: scatter just-finished lanes' results at their queue index.
        # Queue indices are unique per lane, so the row scatters never
        # collide; index M (never-active lanes) drops.
        finished = live & st.done
        tgt = jnp.where(finished, c.qidx, M)
        out_keys = c.out_keys.at[tgt].set(st.top_keys, mode="drop")
        out_scores = c.out_scores.at[tgt].set(st.top_scores, mode="drop")
        out_pulled = c.out_pulled.at[tgt].set(st.n_pulled, mode="drop")
        out_answers = c.out_answers.at[tgt].set(st.n_answers, mode="drop")
        out_iters = c.out_iters.at[tgt].set(st.n_iters, mode="drop")
        out_wasted = c.out_wasted.at[
            jnp.where(live, M, c.qidx)].add(1, mode="drop")

        # Admit: the i-th finished lane (in lane order) takes queue entry
        # next_idx + i while entries remain; later finishers go idle.
        cand = c.next_idx + jnp.cumsum(finished.astype(jnp.int32)) - 1
        refill = finished & (cand < M)

        def do_refill(args):
            st, streams, qidx = args
            with jax.named_scope(scopes.ADMIT):
                fresh = jax.vmap(admit)(jnp.clip(cand, 0, M - 1))
                st2, streams2 = _splice_lanes(st, streams, fresh, refill)
                return st2, streams2, jnp.where(refill, cand, qidx).astype(
                    jnp.int32)

        # The cond skips the per-lane re-gather on the (common) trips
        # where no lane finished.
        st, streams, qidx = jax.lax.cond(
            jnp.any(refill), do_refill, lambda args: args,
            (st, c.streams, c.qidx))

        return _RefillCarry(
            st=st, streams=streams, qidx=qidx,
            next_idx=c.next_idx + jnp.sum(refill).astype(jnp.int32),
            out_keys=out_keys, out_scores=out_scores,
            out_pulled=out_pulled, out_answers=out_answers,
            out_iters=out_iters, out_wasted=out_wasted,
            trips=c.trips + 1)

    return jax.lax.while_loop(
        lambda c: jnp.any(~c.st.done) & (c.trips < trips_cap),
        body, carry0)


def execute_queue(store: TripleStore, relax: RelaxTable,
                  queue_pids: jax.Array, queue_masks: jax.Array,
                  cfg: EngineConfig, lanes: int) -> EngineResult:
    """Execute an (M, T) query queue under precomputed (M, T, R) plans.

    The single funnel into ``_execute_refill``: every entry point —
    ``run_query`` (M = lanes = 1), ``run_query_batch[_with_masks]``
    (lanes = M), ``run_query_stream[_with_masks]`` (lanes free), and the
    sharded ``distributed._shard_body`` — builds its call here, so there
    is exactly one loop body (``_step``) to test, profile, and port to
    Pallas. Returns an ``EngineResult`` whose fields carry a leading (M,)
    axis in queue order.
    """
    fin = _execute_refill(store, relax, queue_pids, queue_masks, cfg, lanes)
    return EngineResult(
        keys=fin.out_keys, scores=fin.out_scores, n_pulled=fin.out_pulled,
        n_answers=fin.out_answers, n_iters=fin.out_iters,
        n_wasted=fin.out_wasted, relax_mask=queue_masks)


def plan_for_mode(store: TripleStore, relax: RelaxTable,
                  pattern_ids: jax.Array, cfg: EngineConfig,
                  mode: str) -> jax.Array:
    """The (T, R) relaxation mask for one query under ``mode``.

    mode ∈ {"trinit", "specqp", "specqp_pattern", "join_only"}.
    """
    R = relax.ids.shape[1]
    if mode == "trinit":
        return plangen.trinit_plan(pattern_ids, R)
    if mode == "specqp":
        return plangen.plan(store, relax, pattern_ids, cfg.k, cfg.grid_bins,
                            cfg.plan_slack, cfg.cardinality_mode)
    if mode == "specqp_pattern":
        return plangen.per_pattern_plan(
            plangen.plan(store, relax, pattern_ids, cfg.k, cfg.grid_bins,
                         cfg.plan_slack, cfg.cardinality_mode))
    if mode == "join_only":
        return jnp.zeros((pattern_ids.shape[0], R), dtype=bool)
    raise ValueError(mode)


def plans_by_bitmap(store: TripleStore, cfg: EngineConfig,
                    mode: str) -> bool:
    """Whether ``plan_for_mode`` counts its joins by popcount over the
    store's key bitmaps: the exact planner of a Spec-QP mode on a store
    that holds them. False for the binary-search fallback, sketch mode and
    the modes that plan nothing."""
    return (mode in ("specqp", "specqp_pattern")
            and cfg.cardinality_mode == "exact"
            and store.key_bits.shape[-1] > 0)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def run_query(store: TripleStore, relax: RelaxTable, pattern_ids: jax.Array,
              cfg: EngineConfig, mode: str = "specqp") -> EngineResult:
    """Answer one star query.

    mode ∈ {"trinit", "specqp", "specqp_pattern", "join_only"}.

    A degenerate configuration of the unified executor: a depth-1 queue
    on a single lane (``n_wasted`` is identically 0 — the loop exits the
    trip the query finishes).
    """
    mask = plan_for_mode(store, relax, pattern_ids, cfg, mode)
    res = execute_queue(store, relax, pattern_ids[None], mask[None],
                        cfg, lanes=1)
    return jax.tree_util.tree_map(lambda x: x[0], res)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def plan_query_batch(store, relax, pattern_ids_batch, cfg: EngineConfig,
                     mode: str = "specqp") -> jax.Array:
    """(Q, T, R) plans for a (Q, T) query batch — the serving layer's plan
    phase. Splitting planning from execution lets the scheduler compose
    micro-batches by *planned* work (sum of enabled source lengths), which
    is what keeps lockstep waste low in ``launch.batching``."""
    return jax.vmap(
        lambda pids: plan_for_mode(store, relax, pids, cfg, mode)
    )(pattern_ids_batch)


@partial(jax.jit, static_argnames=("cfg",))
def run_query_batch_with_masks(store, relax, pattern_ids_batch,
                               masks: jax.Array,
                               cfg: EngineConfig) -> EngineResult:
    """Execute a (Q, T) batch under precomputed (Q, T, R) plans.

    Fixed-batch degenerate configuration of the unified executor: one
    lane per queue entry, so every query is admitted up front and the
    splice path never fires — finished lanes freeze until the batch tail,
    and per-lane ``n_wasted`` counts the frozen lockstep trips.
    """
    Q = pattern_ids_batch.shape[0]
    return execute_queue(store, relax, pattern_ids_batch, masks, cfg,
                         lanes=Q)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def run_query_batch(store, relax, pattern_ids_batch, cfg: EngineConfig,
                    mode: str = "specqp") -> EngineResult:
    """Answer a (Q, T) batch of star queries (fixed-batch configuration).

    Planning and stream gathering vmap per lane; execution runs under ONE
    while_loop with lane-masked early exit (the unified executor at
    lanes = Q), so a fast lane stops pulling/merging the moment its own
    HRJN bound closes instead of shadow-executing until the slowest lane
    terminates. Results are element-wise identical to per-query
    ``run_query`` (the serving layer's correctness contract; see
    tests/test_serving.py and tests/test_executor_equiv.py), and per-lane
    ``n_wasted`` exposes the residual lockstep cost.
    """
    masks = jax.vmap(
        lambda pids: plan_for_mode(store, relax, pids, cfg, mode)
    )(pattern_ids_batch)
    return run_query_batch_with_masks.__wrapped__(
        store, relax, pattern_ids_batch, masks, cfg)


@partial(jax.jit, static_argnames=("cfg", "lanes"))
def run_query_stream_with_masks(store, relax, pattern_ids_queue,
                                masks: jax.Array, cfg: EngineConfig,
                                lanes: int = 8) -> EngineResult:
    """Serve an (M, T) query queue under precomputed (M, T, R) plans
    through ``lanes`` continuous-refill device lanes (``_execute_refill``).

    Results carry a leading (M,) axis in queue order. Top-k keys/scores
    and the n_pulled/n_answers/n_iters counters are element-wise identical
    to per-query ``run_query``; ``n_wasted`` is the drain accounting (idle
    trips of the serving lane, attributed to its last query)."""
    return execute_queue(store, relax, pattern_ids_queue, masks, cfg,
                         lanes)


@partial(jax.jit, static_argnames=("cfg", "mode", "lanes"))
def run_query_stream(store, relax, pattern_ids_queue, cfg: EngineConfig,
                     mode: str = "specqp", lanes: int = 8) -> EngineResult:
    """Plan + stream-execute an (M, T) query queue in one jit call.

    The streaming analogue of ``run_query_batch``: instead of freezing a
    finished lane until the batch tail, the executor splices the next
    queued query into the freed lane, so M can far exceed ``lanes`` and
    lockstep waste shrinks to the end-of-stream drain.
    """
    masks = jax.vmap(
        lambda pids: plan_for_mode(store, relax, pids, cfg, mode)
    )(pattern_ids_queue)
    return run_query_stream_with_masks.__wrapped__(
        store, relax, pattern_ids_queue, masks, cfg, lanes)


@partial(jax.jit, static_argnames=("k", "n_entities"))
def naive_full_scan(store: TripleStore, relax: RelaxTable,
                    pattern_ids: jax.Array, k: int, n_entities: int,
                    relax_mask: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Exact oracle (and the paper-intro naive baseline): materialize every
    relaxed answer and sort. Per pattern, an answer key's contribution is the
    max weighted score over {original} ∪ relaxations (Definition 8's max over
    rewritings distributes over the star-join sum).

    ``relax_mask`` optionally disables relaxations: (T, R) per-relaxation,
    or (T,) per-pattern (broadcast over R) — used to compute which patterns
    TRULY require relaxation (Table 3 ground truth)."""
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    active = pattern_ids != PAD_KEY
    safe_pid = jnp.where(active, pattern_ids, 0)
    if relax_mask is None:
        relax_mask = jnp.ones((T, R), bool)
    elif relax_mask.ndim == 1:
        relax_mask = jnp.broadcast_to(relax_mask[:, None], (T, R))

    def best_per_key(pid, use_relax):
        rel_ids = jnp.where(use_relax, relax.ids[pid], PAD_KEY)
        rel_w = relax.weights[pid]
        src_ids = jnp.concatenate([pid[None], jnp.where(
            rel_ids == PAD_KEY, 0, rel_ids)])
        weights = jnp.concatenate([jnp.ones((1,), jnp.float32), rel_w])
        src_ok = jnp.concatenate([jnp.array([True]), rel_ids != PAD_KEY])
        best = jnp.full((n_entities,), NEG_INF, jnp.float32)
        present = jnp.zeros((n_entities,), bool)

        def body(carry, r):
            best, present = carry
            keys = store.keys[src_ids[r]]
            sc = store.scores[src_ids[r]] * weights[r]
            ok = (keys != PAD_KEY) & src_ok[r]
            idx = jnp.where(ok, keys, 0)
            best = best.at[idx].max(jnp.where(ok, sc, NEG_INF), mode="drop")
            present = present.at[idx].max(ok, mode="drop")
            return (best, present), None

        (best, present), _ = jax.lax.scan(
            body, (best, present), jnp.arange(R + 1))
        return jnp.where(present, best, NEG_INF), present

    best_t, present_t = jax.vmap(best_per_key)(safe_pid, relax_mask)
    all_present = jnp.all(present_t | ~active[:, None], axis=0)
    total = jnp.sum(jnp.where(active[:, None], jnp.where(
        present_t, best_t, 0.0), 0.0), axis=0)
    total = jnp.where(all_present, total, NEG_INF)
    top_s, top_i = jax.lax.top_k(total, k)
    top_keys = jnp.where(top_s > NEG_INF, top_i.astype(jnp.int32), PAD_KEY)
    return top_keys, top_s
