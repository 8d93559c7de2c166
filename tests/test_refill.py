"""Continuous-refill streaming executor: differential serving tests.

The refill executor's contract (DESIGN.md §8) extends the serving layer's:
streaming is a *pure throughput transform*. Per-query top-k keys/scores and
work counters are element-wise identical to sequential ``engine.run_query``
across engine modes, ragged arrival orders, queue lengths that are not a
multiple of the lane count, and the single-lane degenerate config. Lane
*recycling* must be leak-proof: a spliced lane's seen ring / cursors /
top-k start from scratch, so a key the previous occupant pulled (or
evicted from a wrapped ring) can never reach the new query's merge.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from conftest import small_workload, TEST_GRID_BINS
from repro.core import engine
from repro.core import operators as ops
from repro.core.types import EngineConfig
from repro.launch import batching

CFG = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
MODES = ("trinit", "specqp", "specqp_pattern", "join_only")


def _singles(wl, idxs, mode, cfg=CFG):
    return [engine.run_query(wl.store, wl.relax, jnp.asarray(wl.queries[i]),
                             cfg, mode) for i in idxs]


def _assert_stream_equals_singles(res, singles, ctx=""):
    for i, s in enumerate(singles):
        np.testing.assert_array_equal(np.asarray(res.keys[i]),
                                      np.asarray(s.keys),
                                      err_msg=f"{ctx} query {i}")
        np.testing.assert_array_equal(np.asarray(res.scores[i]),
                                      np.asarray(s.scores))
        assert int(res.n_iters[i]) == int(s.n_iters), (ctx, i)
        assert int(res.n_pulled[i]) == int(s.n_pulled), (ctx, i)
        assert int(res.n_answers[i]) == int(s.n_answers), (ctx, i)


@pytest.mark.parametrize("mode", MODES)
def test_stream_equals_single_every_mode(mode):
    """Q=8 queries through 3 lanes (Q not a multiple of the lane count):
    every per-query output equals sequential run_query, element-wise."""
    wl = small_workload(seed=0, n_queries=8)
    qs = jnp.asarray(wl.queries)
    res = engine.run_query_stream(wl.store, wl.relax, qs, CFG, mode,
                                  lanes=3)
    _assert_stream_equals_singles(res, _singles(wl, range(8), mode), mode)


def test_stream_single_lane_degenerate():
    """lanes=1 serializes the queue through one lane — still exact, and
    with nothing to wait for, zero wasted trips on every query."""
    wl = small_workload(seed=0, n_queries=8)
    qs = jnp.asarray(wl.queries)
    res = engine.run_query_stream(wl.store, wl.relax, qs, CFG, "specqp",
                                  lanes=1)
    _assert_stream_equals_singles(res, _singles(wl, range(8), "specqp"),
                                  "lanes=1")
    assert (np.asarray(res.n_wasted) == 0).all()


def test_stream_lanes_exceed_queue():
    """More lanes than queue entries: surplus lanes idle from trip one and
    must not touch (or double-emit into) any real query's output."""
    wl = small_workload(seed=0, n_queries=8)
    qs = jnp.asarray(wl.queries[:3])
    res = engine.run_query_stream(wl.store, wl.relax, qs, CFG, "specqp",
                                  lanes=8)
    _assert_stream_equals_singles(res, _singles(wl, range(3), "specqp"),
                                  "lanes>M")


def test_stream_uniform_queue_zero_waste():
    """All lanes finish together (identical queries, M == lanes): the drain
    is empty, so every per-query n_wasted is exactly zero."""
    wl = small_workload(seed=0, n_queries=8)
    qs = jnp.asarray(np.repeat(wl.queries[:1], 3, axis=0))
    res = engine.run_query_stream(wl.store, wl.relax, qs, CFG, "specqp",
                                  lanes=3)
    assert (np.asarray(res.n_wasted) == 0).all()
    _assert_stream_equals_singles(res, _singles(wl, [0, 0, 0], "specqp"),
                                  "uniform")


def _refill_executor(wl, mode="specqp", lanes=2, refill_depth=8,
                     pipeline=False):
    bcfg = batching.BatchingConfig(
        max_batch=4, max_wait_s=0.01, q_buckets=(1, 4, 8),
        t_buckets=(2, 3), refill=True, lanes=lanes,
        refill_depth=refill_depth, pipeline=pipeline)
    return batching.BatchExecutor(wl.store, wl.relax, CFG, mode, bcfg)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=5),
       n=st.integers(min_value=1, max_value=10),
       lanes=st.sampled_from((1, 2, 4)),
       mode=st.sampled_from(("specqp", "trinit", "join_only")))
def test_refill_executor_ragged_arrivals_property(seed, n, lanes, mode):
    """Randomized ragged arrival orders (duplicates included, n not tied
    to the lane count) through the bucketed refill pipeline == per-query
    run_query."""
    wl = small_workload(seed=0, n_queries=8)
    rng = np.random.default_rng(seed)
    idxs = rng.choice(len(wl.queries), size=n, replace=True)
    queries = [np.asarray(wl.queries[i]) for i in idxs]
    ex = _refill_executor(wl, mode, lanes=lanes)
    results = ex.run(queries)
    for r, i in zip(results, idxs):
        s = engine.run_query(wl.store, wl.relax, jnp.asarray(wl.queries[i]),
                             CFG, mode)
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))
        assert r.n_iters == int(s.n_iters)


def test_refill_pipeline_equivalence():
    """The double-buffered plan/execute path returns the same per-request
    results as the unpipelined one (and as run_query)."""
    wl = small_workload(seed=2, n_queries=8)
    queries = [np.asarray(q) for q in wl.queries]
    res_pipe = _refill_executor(wl, pipeline=True).run(queries)
    singles = _singles(wl, range(len(queries)), "specqp")
    for r, s in zip(res_pipe, singles):
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))


def test_refill_microbatcher_threaded():
    """Futures from the threaded queue over a refill executor resolve to
    per-query results (the flush group becomes the admission queue)."""
    wl = small_workload(seed=0, n_queries=8)
    queries = [np.asarray(q) for q in wl.queries]
    ex = _refill_executor(wl, "specqp")
    with batching.MicroBatcher(ex) as mb:
        futs = [mb.submit(q) for q in queries]
        results = [f.result(timeout=120) for f in futs]
    for r, s in zip(results, _singles(wl, range(len(queries)), "specqp")):
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))


# ---------------------------------------------------------------------------
# Lane recycling: the state splice must be leak-proof.
# ---------------------------------------------------------------------------

def _ring_kg():
    """KG engineered so stream 0 of query [0, 1] pulls ≥ 3× a tiny seen
    cap (the ring wraps ≥ 2×, evicting early keys) before its bound
    closes — shared with the cross-executor differential suite (the
    construction lives in tests/harness.py), reused here to stress-test
    lane *recycling*: a query spliced into that lane re-pulls exactly
    the keys the previous occupant pulled and evicted."""
    from harness import ring_kg
    return ring_kg()


def test_lane_recycling_after_wrapped_ring():
    """Queue [A, A, B] through ONE lane with a tiny seen cap: query A
    wraps its seen ring ≥ 2× (evicting the keys it pulled first), then
    the SAME query is spliced into the recycled lane and re-pulls every
    evicted key, then a distinct query B probes a key A also pulled.
    Any stale lane state — a leftover seen entry marking a key already
    emitted, a non-zero cursor, a surviving top-k slot — would change the
    second run's dedup/merge and break element-wise equality with the
    fresh single-query runs."""
    store, relax = _ring_kg()
    cfg = EngineConfig(block=8, k=5, grid_bins=TEST_GRID_BINS, seen_cap=16)
    qa = jnp.asarray([0, 1], jnp.int32)
    qb = jnp.asarray([2, 1], jnp.int32)
    queue = jnp.stack([qa, qa, qb])
    res = engine.run_query_stream(store, relax, queue, cfg, "trinit",
                                  lanes=1)
    sa = engine.run_query(store, relax, qa, cfg, "trinit")
    sb = engine.run_query(store, relax, qb, cfg, "trinit")
    # The ring really wrapped ≥ 2× before the first refill.
    assert int(sa.n_pulled) >= 3 * 16
    for i, s in enumerate((sa, sa, sb)):
        np.testing.assert_array_equal(np.asarray(res.keys[i]),
                                      np.asarray(s.keys), err_msg=f"q{i}")
        np.testing.assert_array_equal(np.asarray(res.scores[i]),
                                      np.asarray(s.scores))
        assert int(res.n_pulled[i]) == int(s.n_pulled), i
        assert int(res.n_iters[i]) == int(s.n_iters), i
    # And the answers are right, not merely self-consistent.
    bk, _ = engine.naive_full_scan(store, relax, qa, cfg.k, 6000)
    np.testing.assert_array_equal(np.asarray(bk), np.asarray(res.keys[1]))


def test_splice_fully_resets_lane_state():
    """Unit test of the splice itself: every _LoopState field of a
    refilled lane equals its _init_state value and the lane's streams are
    replaced; the untouched lane keeps its (garbage) state bit-for-bit."""
    wl = small_workload(seed=0, n_queries=4)
    qs = jnp.asarray(wl.queries[:2])
    masks = engine.plan_query_batch(wl.store, wl.relax, qs, CFG, "trinit")
    streams = jax.vmap(
        lambda pids, m: ops.gather_streams(wl.store, wl.relax, pids, m)
    )(qs, masks)
    T, R1, L = streams.keys.shape[1:]
    N = engine._seen_size(R1, L, CFG)
    k = CFG.k

    rng = np.random.default_rng(7)
    garbage = engine._LoopState(
        cursors=jnp.asarray(rng.integers(1, L, (2, T, R1)), jnp.int32),
        seen_keys=jnp.asarray(rng.integers(0, 100, (2, T, N)), jnp.int32),
        seen_scores=jnp.asarray(rng.random((2, T, N)), jnp.float32),
        seen_cnt=jnp.asarray(rng.integers(1, N, (2, T)), jnp.int32),
        top_keys=jnp.asarray(rng.integers(0, 100, (2, k)), jnp.int32),
        top_scores=jnp.asarray(rng.random((2, k)), jnp.float32),
        n_pulled=jnp.asarray([17, 23], jnp.int32),
        n_answers=jnp.asarray([5, 6], jnp.int32),
        n_iters=jnp.asarray([9, 11], jnp.int32),
        n_wasted=jnp.asarray([1, 2], jnp.int32),
        done=jnp.asarray([True, True]))
    fresh = jax.tree_util.tree_map(lambda x: x[::-1], streams)
    refill = jnp.asarray([True, False])
    new_st, new_streams = engine._splice_lanes(garbage, streams, fresh,
                                               refill)

    init = engine._init_state(T, R1, N, k)
    # Lane 0: spliced — complete re-init + fresh streams.
    for name in engine._LoopState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(new_st, name)[0]),
            np.asarray(getattr(init, name)), err_msg=f"lane0 {name}")
    np.testing.assert_array_equal(np.asarray(new_streams.keys[0]),
                                  np.asarray(fresh.keys[0]))
    # Lane 1: untouched — garbage preserved bit-for-bit, streams kept.
    for name in engine._LoopState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(new_st, name)[1]),
            np.asarray(getattr(garbage, name)[1]), err_msg=f"lane1 {name}")
    np.testing.assert_array_equal(np.asarray(new_streams.keys[1]),
                                  np.asarray(streams.keys[1]))
