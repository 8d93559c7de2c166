"""Serving layer: batched-vs-sequential equivalence, buckets, micro-batching.

The correctness contract of the whole serving subsystem (DESIGN.md §8) is
that batching is a *pure throughput transform*: per-request top-k keys and
scores are element-wise identical to per-query ``engine.run_query``, across
engine modes, ragged batches (T-bucket padding), batch-size padding lanes,
and the threaded micro-batcher.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from conftest import small_workload, TEST_GRID_BINS
from repro.core import engine
from repro.core.types import EngineConfig, PAD_KEY
from repro.launch import batching

CFG = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
MODES = ("trinit", "specqp", "specqp_pattern", "join_only")


def _singles(wl, idxs, mode):
    return [engine.run_query(wl.store, wl.relax, jnp.asarray(wl.queries[i]),
                             CFG, mode) for i in idxs]


@pytest.mark.parametrize("mode", MODES)
def test_batch_equals_single_exactly(mode):
    """run_query_batch == per-query run_query, element-wise, every mode."""
    wl = small_workload(seed=0, n_queries=8)
    qs = jnp.asarray(wl.queries)          # ragged Ts, -1 padded rows
    batch = engine.run_query_batch(wl.store, wl.relax, qs, CFG, mode)
    for i, single in enumerate(_singles(wl, range(len(wl.queries)), mode)):
        np.testing.assert_array_equal(np.asarray(batch.keys[i]),
                                      np.asarray(single.keys))
        np.testing.assert_array_equal(np.asarray(batch.scores[i]),
                                      np.asarray(single.scores))
        # Early-exit lanes: frozen counters equal the single-query run's.
        assert int(batch.n_iters[i]) == int(single.n_iters)
        assert int(batch.n_pulled[i]) == int(single.n_pulled)
        assert int(batch.n_answers[i]) == int(single.n_answers)


def test_lockstep_accounting():
    """Every lane's useful + wasted trips equal the batch's trip count."""
    wl = small_workload(seed=1, n_queries=8)
    qs = jnp.asarray(wl.queries)
    batch = engine.run_query_batch(wl.store, wl.relax, qs, CFG, "specqp")
    it = np.asarray(batch.n_iters)
    w = np.asarray(batch.n_wasted)
    total = it + w
    assert (total == total[0]).all()
    assert int(total[0]) == int(it.max())
    # The slowest lane never waits.
    assert w[int(np.argmax(it))] == 0


def test_pad_lanes_are_inert():
    """All-PAD batch lanes finish on their first trip and return no keys."""
    wl = small_workload(seed=0, n_queries=4)
    qs = np.asarray(wl.queries[:2])
    padded = np.concatenate(
        [qs, np.full((2, qs.shape[1]), int(PAD_KEY), np.int32)])
    batch = engine.run_query_batch(wl.store, wl.relax, jnp.asarray(padded),
                                   CFG, "specqp")
    ref = engine.run_query_batch(wl.store, wl.relax, jnp.asarray(qs),
                                 CFG, "specqp")
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(batch.keys[i]),
                                      np.asarray(ref.keys[i]))
        np.testing.assert_array_equal(np.asarray(batch.scores[i]),
                                      np.asarray(ref.scores[i]))
    for i in (2, 3):
        assert (np.asarray(batch.keys[i]) == int(PAD_KEY)).all()
        assert int(batch.n_iters[i]) == 1
        assert int(batch.n_pulled[i]) == 0


def test_plan_then_execute_equals_fused():
    """plan_query_batch + run_query_batch_with_masks == run_query_batch."""
    wl = small_workload(seed=2, n_queries=6)
    qs = jnp.asarray(wl.queries[:4])
    fused = engine.run_query_batch(wl.store, wl.relax, qs, CFG, "specqp")
    masks = engine.plan_query_batch(wl.store, wl.relax, qs, CFG, "specqp")
    split = engine.run_query_batch_with_masks(wl.store, wl.relax, qs,
                                              masks, CFG)
    np.testing.assert_array_equal(np.asarray(fused.keys),
                                  np.asarray(split.keys))
    np.testing.assert_array_equal(np.asarray(fused.scores),
                                  np.asarray(split.scores))
    np.testing.assert_array_equal(np.asarray(fused.relax_mask),
                                  np.asarray(split.relax_mask))


def _executor(wl, mode="specqp", max_batch=4):
    bcfg = batching.BatchingConfig(max_batch=max_batch, max_wait_s=0.01,
                                   q_buckets=(1, 4), t_buckets=(2, 3))
    return batching.BatchExecutor(wl.store, wl.relax, CFG, mode, bcfg)


@pytest.mark.parametrize("mode", ("specqp", "trinit"))
def test_offline_executor_equivalence(mode):
    """BatchExecutor.run (bucketing, padding, plan-ahead scheduling) is
    element-wise identical to the sequential loop — including a ragged
    request count that forces a partially-padded q bucket."""
    wl = small_workload(seed=0, n_queries=10)
    queries = [np.asarray(q) for q in wl.queries]   # 10 = 2×4 + a 2-pad
    ex = _executor(wl, mode)
    results = ex.run(queries)
    singles = _singles(wl, range(len(queries)), mode)
    for i, (r, s) in enumerate(zip(results, singles)):
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys),
                                      err_msg=f"query {i}")
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))
        assert r.n_iters == int(s.n_iters)
        assert r.n_pulled == int(s.n_pulled)
        T = int((queries[i] != int(PAD_KEY)).sum())
        np.testing.assert_array_equal(
            r.relax_mask, np.asarray(s.relax_mask)[:T])
    assert ex.stats, "executor recorded no batch stats"
    assert sum(s.n_requests for s in ex.stats) == len(queries)
    assert 0.0 <= ex.wasted_fraction() < 1.0


@pytest.mark.parametrize("mode,cardinality,key_bits,expect", [
    ("specqp", "exact", True, True),
    ("specqp", "exact", False, False),      # binary-search fallback
    ("specqp", "sketch", True, False),
    ("trinit", "exact", True, False),       # plans nothing
])
def test_served_result_reports_bitmap_planning(mode, cardinality, key_bits,
                                               expect):
    """``ServedResult.planned_by_bitmap`` is true exactly where the exact
    planner counted by popcount over the store's key bitmaps; the plans
    of the two exact representations are the same bits."""
    import dataclasses
    wl = small_workload(seed=0, n_queries=4)
    assert wl.store.key_bits.shape[-1] > 0
    store = wl.store if key_bits else dataclasses.replace(
        wl.store, key_bits=jnp.zeros((wl.store.keys.shape[0], 0), jnp.uint32))
    cfg = dataclasses.replace(CFG, cardinality_mode=cardinality)
    bcfg = batching.BatchingConfig(max_batch=4, max_wait_s=0.01,
                                   q_buckets=(1, 4), t_buckets=(2, 3))
    queries = [np.asarray(q) for q in wl.queries]
    results = batching.BatchExecutor(store, wl.relax, cfg, mode,
                                     bcfg).run(queries)
    assert [r.planned_by_bitmap for r in results] == [expect] * len(queries)
    if cardinality == "exact":
        for r, q in zip(results, queries):
            s = engine.run_query(wl.store, wl.relax, jnp.asarray(q), CFG,
                                 mode)
            T = int((q != int(PAD_KEY)).sum())
            np.testing.assert_array_equal(r.relax_mask,
                                          np.asarray(s.relax_mask)[:T])


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=3),
       n=st.integers(min_value=1, max_value=7),
       mode=st.sampled_from(("specqp", "join_only")))
def test_offline_executor_equivalence_property(seed, n, mode):
    """Random request subsets through the bucketed pipeline == per-query."""
    wl = small_workload(seed=0, n_queries=8)
    rng = np.random.default_rng(seed)
    idxs = rng.choice(len(wl.queries), size=n, replace=True)
    queries = [np.asarray(wl.queries[i]) for i in idxs]
    ex = _executor(wl, mode)
    results = ex.run(queries)
    for r, i in zip(results, idxs):
        s = engine.run_query(wl.store, wl.relax, jnp.asarray(wl.queries[i]),
                             CFG, mode)
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))


def test_microbatcher_threaded_equivalence():
    """Futures from the threaded queue resolve to per-query results."""
    wl = small_workload(seed=0, n_queries=8)
    queries = [np.asarray(q) for q in wl.queries]
    ex = _executor(wl, "specqp")
    with batching.MicroBatcher(ex) as mb:
        futs = [mb.submit(q) for q in queries]
        results = [f.result(timeout=120) for f in futs]
    singles = _singles(wl, range(len(queries)), "specqp")
    for r, s in zip(results, singles):
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))


def test_microbatcher_survives_bad_request():
    """A query exceeding the largest T bucket fails ITS future with the
    bucketing error; the worker thread survives and later submits still
    resolve (regression: an escaping exception used to kill the loop and
    strand every pending future)."""
    wl = small_workload(seed=0, n_queries=4)
    ex = _executor(wl, "join_only")       # t_buckets=(2, 3)
    good = np.asarray(wl.queries[0])
    too_wide = np.arange(5, dtype=np.int32)   # T=5 > max bucket 3
    with batching.MicroBatcher(ex) as mb:
        bad_fut = mb.submit(too_wide)
        with pytest.raises(ValueError):
            bad_fut.result(timeout=120)
        ok_fut = mb.submit(good)
        r = ok_fut.result(timeout=120)
    s = engine.run_query(wl.store, wl.relax, jnp.asarray(good), CFG,
                         "join_only")
    np.testing.assert_array_equal(r.keys, np.asarray(s.keys))


def test_refill_wasted_leq_fixed_on_skew():
    """Lockstep accounting on the refill path: on a skewed workload the
    streaming executor's total wasted trips never exceed the fixed-batch
    executor's (a finished lane takes new work instead of freezing), and
    when every lane finishes together there is no waste at all. Totals
    come from the executor's running counters, which — unlike summing
    per-request n_wasted — include drain trips attributed to pad queue
    entries (both executors run the same queries, so the useful totals
    match and the wasted totals are directly comparable)."""
    wl = small_workload(seed=1, n_queries=8)
    queries = [np.asarray(q) for q in wl.queries]
    fixed = _executor(wl, "specqp")
    rcfg = batching.BatchingConfig(
        max_batch=4, max_wait_s=0.01, q_buckets=(1, 4, 8),
        t_buckets=(2, 3), refill=True, lanes=4, refill_depth=8)
    refill = batching.BatchExecutor(wl.store, wl.relax, CFG, "specqp",
                                    rcfg)
    rf = refill.run(queries)
    fx = fixed.run(queries)
    for r, f in zip(rf, fx):
        np.testing.assert_array_equal(r.keys, f.keys)
    assert refill._useful_total == fixed._useful_total
    assert refill._wasted_total <= fixed._wasted_total, (
        f"refill wasted {refill._wasted_total} > fixed "
        f"{fixed._wasted_total}")
    # Uniform queue, M == lanes: all lanes close together, zero waste.
    refill.reset_stats()
    refill.run([np.asarray(wl.queries[0])] * 4)
    assert refill._wasted_total == 0


def test_microbatcher_close_drains_pending():
    """close() resolves every future submitted before (or racing with)
    shutdown — with a result or the closed-rejection — and no future
    hangs forever. Regression: requests enqueued behind the stop sentinel
    used to be stranded unresolved."""
    import threading

    wl = small_workload(seed=0, n_queries=4)
    ex = _executor(wl, "join_only")
    mb = batching.MicroBatcher(ex)
    q = np.asarray(wl.queries[0])
    futs, stop = [], threading.Event()

    def submitter():
        while not stop.is_set():
            futs.append(mb.submit(q))

    th = threading.Thread(target=submitter)
    th.start()
    while len(futs) < 8:       # let a backlog build behind the worker
        pass
    mb.close()                 # races with in-flight submits
    stop.set()
    th.join()
    mb.close()                 # idempotent
    s = engine.run_query(wl.store, wl.relax, jnp.asarray(q), CFG,
                         "join_only")
    n_served = 0
    for f in futs:
        assert f.done(), "future left unresolved after close()"
        if f.exception() is None:
            np.testing.assert_array_equal(f.result().keys,
                                          np.asarray(s.keys))
            n_served += 1
        else:
            assert isinstance(f.exception(), RuntimeError)
    assert n_served >= 8       # the pre-close backlog was served, not lost
    # After close, submit fails fast instead of hanging.
    late = mb.submit(q)
    assert late.done() and isinstance(late.exception(), RuntimeError)


def test_executor_stats_consistent_under_concurrency():
    """The stats counters survive the threads that actually touch them:
    a pipelined run (planner thread bumps plan_total_s while the main
    thread records batches) with a reader thread polling the aggregate
    views throughout. Afterwards the running totals must equal the
    per-batch records exactly — the read-modify-write races speclint's
    LD001 guards against would show up here as drift. (Regression:
    plan_total_s was bumped without the lock from the planner thread.)"""
    import threading

    wl = small_workload(seed=0, n_queries=8)
    queries = [np.asarray(q) for q in wl.queries]
    pcfg = batching.BatchingConfig(max_batch=4, max_wait_s=0.01,
                                   q_buckets=(1, 4), t_buckets=(2, 3),
                                   pipeline=True)
    ex = batching.BatchExecutor(wl.store, wl.relax, CFG, "specqp", pcfg)
    errs, stop = [], threading.Event()

    def poller():
        try:
            while not stop.is_set():
                assert 0.0 <= ex.wasted_fraction() <= 1.0
                assert ex.plan_total_s >= 0.0
        except Exception as e:  # noqa: BLE001 — surface on the main thread
            errs.append(e)

    th = threading.Thread(target=poller)
    th.start()
    try:
        results = ex.run(queries)
    finally:
        stop.set()
        th.join()
    assert not errs, errs
    # Pipelined == sequential, still.
    for r, s in zip(results, _singles(wl, range(len(queries)), "specqp")):
        np.testing.assert_array_equal(r.keys, np.asarray(s.keys))
        np.testing.assert_array_equal(r.scores, np.asarray(s.scores))
    # Running totals agree exactly with the per-batch records.
    assert ex._useful_total == sum(s.useful_iters for s in ex.stats)
    assert ex._wasted_total == sum(s.wasted_iters for s in ex.stats)
    assert ex.plan_total_s > 0.0   # planner thread's time was not lost
    ex.reset_stats()
    assert ex.plan_total_s == 0.0 and ex.wasted_fraction() == 0.0


def test_bucket_helpers():
    assert batching.bucket_for(1, (1, 4, 16)) == 1
    assert batching.bucket_for(5, (1, 4, 16)) == 16
    with pytest.raises(ValueError):
        batching.bucket_for(17, (1, 4, 16))
    assert batching.default_t_buckets(4) == (2, 4)
    assert batching.default_t_buckets(2) == (2,)
    # Derived buckets are a power-of-two cover, never t verbatim — with
    # t_buckets=None, distinct Ts must share buckets or every pattern
    # count becomes its own jit specialization.
    assert batching.default_t_buckets(7) == (2, 4, 8)
    assert batching.default_t_buckets(9) == (2, 4, 8, 16)
