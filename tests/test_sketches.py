"""Sketch subsystem: estimator accuracy, sound zeros, planner agreement."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from conftest import small_workload, TEST_GRID_BINS
from repro.core import estimator, kg, plangen, sketches
from repro.core.types import PAD_KEY


def _store_from(lists, list_len=None):
    # Property tests pin list_len so every random example shares one padded
    # shape — one jit specialization instead of one per drawn list length.
    return kg.build_store([(np.asarray(k, np.int32),
                            np.asarray(s, np.float64)) for k, s in lists],
                          list_len=list_len)


def _random_overlapping_lists(rng, n_sets, n_entities, shared, own_max):
    """n_sets key lists sharing ``shared`` keys plus random residuals."""
    common = rng.choice(n_entities, size=shared, replace=False)
    lists = []
    for _ in range(n_sets):
        own = rng.choice(n_entities, size=int(rng.integers(5, own_max)),
                        replace=False)
        keys = np.unique(np.concatenate([common, own]))
        lists.append((keys, rng.random(len(keys)) + 0.1))
    return lists


def test_sketch_shapes_and_determinism():
    store = _store_from([([1, 2, 3], [3, 2, 1]), ([4, 5], [2, 1])])
    # Width is sized adaptively from the ingest's longest list (3 keys →
    # the MIN_WORDS floor for this tiny store).
    assert store.sketch.shape == (2, sketches.SKETCH_LANES,
                                  sketches.adaptive_words(3))
    assert store.sketch.dtype == jnp.uint32
    store2 = _store_from([([1, 2, 3], [3, 2, 1]), ([4, 5], [2, 1])])
    np.testing.assert_array_equal(np.asarray(store.sketch),
                                  np.asarray(store2.sketch))
    # An empty pattern has an all-zero signature.
    store3 = _store_from([([], [])])
    assert int(np.asarray(store3.sketch).sum()) == 0


def test_adaptive_words_sizing():
    """W = 2·Lmax pow2-rounded, clamped; fixed default preserved at L=512."""
    assert sketches.adaptive_words(1) == sketches.MIN_WORDS
    assert sketches.adaptive_words(48) == sketches.MIN_WORDS
    # Continuity with the historical fixed default at benchmark scale.
    assert sketches.adaptive_words(512) == sketches.SKETCH_WORDS == 1024
    # The ROADMAP saturation regime: ≫ 2k keys/lane now widens the sketch.
    assert sketches.adaptive_words(8192) == 16384
    assert sketches.adaptive_words(10**7) == sketches.MAX_WORDS
    # Monotone and power-of-two.
    prev = 0
    for L in (1, 10, 100, 1000, 5000, 50_000):
        w = sketches.adaptive_words(L)
        assert w >= prev and (w & (w - 1)) == 0
        prev = w


def test_fixed_width_override_and_shard_geometry():
    """Explicit sketch_words pins geometry; shard stores share one W."""
    lists = [(np.arange(100, dtype=np.int32),
              np.random.default_rng(0).random(100) + 0.1),
             (np.arange(50, 80, dtype=np.int32),
              np.random.default_rng(1).random(30) + 0.1)]
    store = kg.build_store(lists, sketch_words=256)
    assert store.sketch.shape[-1] == 256
    # Sharded build: geometry comes from the GLOBAL longest list, uniform
    # across shards (stacking + psum require it).
    from repro.core import distributed
    skg = distributed.build_sharded_kg(
        lists, kg.build_relax_table(2, {0: [(1, 0.5)]}), n_shards=2)
    assert skg.stores.sketch.shape[2:] == (
        sketches.SKETCH_LANES, sketches.adaptive_words(100))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       shared=st.integers(min_value=0, max_value=80),
       n_sets=st.integers(min_value=2, max_value=4))
def test_intersection_estimate_close_to_exact(seed, shared, n_sets):
    """|est − exact| within ε: max(4, 25% + sqrt noise) of the true size."""
    rng = np.random.default_rng(seed)
    lists = _random_overlapping_lists(rng, n_sets, 4000, shared, 400)
    store = _store_from(lists, list_len=512)
    pids = jnp.arange(n_sets, dtype=jnp.int32)
    active = jnp.ones((n_sets,), bool)
    exact = float(estimator.star_join_cardinality(store, pids, active))
    est = float(sketches.intersection_size(
        store.sketch[pids], store.lengths[pids].astype(jnp.float32), active))
    tol = max(4.0, 0.25 * exact + np.sqrt(exact))
    assert abs(est - exact) <= tol, (exact, est)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_joinability_zero_is_truly_zero(seed):
    """Whenever the raw sketch estimator reports a 0 joinable count, the
    exact count is 0 (zeros come only from the empty-AND-lane proof)."""
    rng = np.random.default_rng(seed)
    # Patterns 0-1 query; 2-4 relaxations of 0; some disjoint, some not.
    base = rng.choice(1000, size=60, replace=False)
    lists = [(base, rng.random(60) + 0.1),
             (rng.choice(1000, size=40, replace=False), rng.random(40) + 0.1)]
    for _ in range(3):
        if rng.random() < 0.5:  # stray: disjoint from everything
            keys = 5000 + rng.choice(1000, size=30, replace=False)
        else:
            keys = rng.choice(1000, size=30, replace=False)
        lists.append((keys, rng.random(30) + 0.1))
    store = _store_from(lists)
    relax = kg.build_relax_table(5, {0: [(2, 0.9), (3, 0.5), (4, 0.3)]})
    pids = jnp.asarray([0, 1], jnp.int32)
    active = jnp.asarray([True, True])
    sk = np.asarray(sketches.sketch_joinable_counts(store, relax, pids,
                                                    active))
    ex = np.asarray(estimator.joinable_counts(store, relax, pids, active))
    assert np.all(ex[sk == 0.0] == 0.0), (sk, ex)


def test_empty_and_lane_proof_zero():
    """Small disjoint key sets estimate exactly 0 via the empty-AND-lane
    proof; larger disjoint sets may carry a sub-key collision residue but
    stay under the joinability rounding threshold's scale."""
    store = _store_from([(np.arange(15), np.random.rand(15) + 0.1),
                         (np.arange(5000, 5015), np.random.rand(15) + 0.1)])
    est = float(sketches.intersection_size(
        store.sketch[:2], store.lengths[:2].astype(jnp.float32),
        jnp.asarray([True, True])))
    assert est == 0.0
    # Bigger disjoint sets: every lane may collide, but the occupancy
    # model attributes the fill to chance — the estimate stays tiny
    # relative to the set sizes.
    store2 = _store_from([(np.arange(100), np.random.rand(100) + 0.1),
                          (np.arange(5000, 5100), np.random.rand(100) + 0.1)])
    est2 = float(sketches.intersection_size(
        store2.sketch[:2], store2.lengths[:2].astype(jnp.float32),
        jnp.asarray([True, True])))
    assert est2 <= 4.0


def test_single_set_and_empty_arity():
    store = _store_from([(np.arange(37), np.random.rand(37) + 0.1)])
    one = float(sketches.intersection_size(
        store.sketch[jnp.asarray([0])],
        store.lengths[jnp.asarray([0])].astype(jnp.float32),
        jnp.asarray([True])))
    assert one == 37.0
    none = float(sketches.intersection_size(
        store.sketch[jnp.asarray([0])],
        store.lengths[jnp.asarray([0])].astype(jnp.float32),
        jnp.asarray([False])))
    assert none == 0.0


def test_sketch_cardinalities_match_exact_on_crafted():
    """On small well-separated lists the sketched (n, n_rel) are within a
    few keys of the exact values (collision mass is negligible there)."""
    store = _store_from([
        ([1, 2, 3, 4, 5], [5, 4, 3, 2, 1]),
        ([2, 3, 4, 9], [9, 5, 2, 1]),
        ([3, 4, 5, 6, 7], [7, 3, 2, 1.5, 1]),   # relaxation of 0
    ])
    relax = kg.build_relax_table(3, {0: [(2, 0.8)]})
    pids = jnp.asarray([0, 1], jnp.int32)
    active = jnp.asarray([True, True])
    n_e, nrel_e = estimator.exact_cardinalities(store, relax, pids, active)
    n_s, nrel_s = sketches.sketch_cardinalities(store, relax, pids, active)
    assert abs(float(n_s) - float(n_e)) <= 1.0
    assert abs(float(nrel_s[0, 0]) - float(nrel_e[0, 0])) <= 1.0
    # Padded relaxation slots stay 0.
    assert float(nrel_s[1, 0]) == 0.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_planner_agreement_sketch_vs_exact(seed):
    """Acceptance: the sketched (T, R) mask agrees with the exact mask on
    ≥ 95% of bits across the synthetic workloads at default W."""
    wl = small_workload(seed=seed, n_queries=8)
    agree = tot = 0
    for i in range(len(wl.queries)):
        q = jnp.asarray(wl.queries[i])
        me = np.asarray(plangen.plan(wl.store, wl.relax, q, 5,
                                     TEST_GRID_BINS, None, "exact"))
        ms = np.asarray(plangen.plan(wl.store, wl.relax, q, 5,
                                     TEST_GRID_BINS, None, "sketch"))
        agree += int((me == ms).sum())
        tot += me.size
    assert agree / tot >= 0.95, f"mask agreement {agree}/{tot}"


def test_sharded_sketch_estimates_sum_to_global():
    """Per-shard sketch estimates psum ≈ the global exact cardinality
    (hash partitioning splits every key set disjointly)."""
    from repro.core import distributed
    rng = np.random.default_rng(3)
    lists = _random_overlapping_lists(rng, 3, 3000, 50, 300)
    n_shards = 4
    stores, _ = distributed.shard_workload(lists, n_shards)
    pids = jnp.asarray([0, 1, 2], jnp.int32)
    active = jnp.ones((3,), bool)
    total = 0.0
    for s in range(n_shards):
        local = jnp.asarray(np.asarray(stores.sketch)[s])
        lens = jnp.asarray(np.asarray(stores.lengths)[s])
        total += float(sketches.intersection_size(
            local[pids], lens[pids].astype(jnp.float32), active))
    g_store = _store_from(lists)
    exact = float(estimator.star_join_cardinality(g_store, pids, active))
    tol = max(4.0, 0.3 * exact + np.sqrt(exact))
    assert abs(total - exact) <= tol, (total, exact)
