"""Estimator: exact cardinalities + PLANGEN inputs (§3.1–3.2)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import estimator, kg, plangen
from repro.core.types import PAD_KEY


def _store_from(lists):
    return kg.build_store([(np.asarray(k, np.int32),
                            np.asarray(s, np.float64)) for k, s in lists])


def test_star_join_cardinality_exact():
    store = _store_from([
        ([1, 2, 3, 4], [4, 3, 2, 1]),
        ([2, 3, 5], [9, 5, 1]),
        ([3, 2, 9, 11], [7, 3, 2, 1]),
    ])
    pids = jnp.asarray([0, 1, 2])
    active = jnp.asarray([True, True, True])
    n = estimator.star_join_cardinality(store, pids, active)
    assert float(n) == 2.0  # {2, 3}
    n2 = estimator.star_join_cardinality(
        store, jnp.asarray([0, 1, 0]), jnp.asarray([True, True, False]))
    assert float(n2) == 2.0  # {2, 3} again (third inactive)


def test_relaxed_cardinality_swaps_pattern():
    store = _store_from([
        ([1, 2, 3], [3, 2, 1]),
        ([2, 3], [5, 1]),
        ([1, 9], [2, 1]),     # relaxation candidate for pattern 1
    ])
    pids = jnp.asarray([0, 1])
    active = jnp.asarray([True, True])
    n = estimator.relaxed_join_cardinality(
        store, pids, active, jnp.int32(1), jnp.int32(2))
    assert float(n) == 1.0  # {1}
    n_pad = estimator.relaxed_join_cardinality(
        store, pids, active, jnp.int32(1), PAD_KEY)
    assert float(n_pad) == 0.0


def test_member_handles_padding():
    store = _store_from([([5, 1, 7], [3, 2, 1])])
    probes = jnp.asarray([1, 5, 7, 8, PAD_KEY], jnp.int32)
    got = estimator.member(store.sorted_keys[0], probes)
    np.testing.assert_array_equal(np.asarray(got),
                                  [True, True, True, False, False])


# ---------------------------------------------------------------------------
# Brute-force numpy cross-checks on random small stores.
# ---------------------------------------------------------------------------

def _random_lists(rng, n_patterns, n_entities=64, max_len=24):
    lists = []
    for _ in range(n_patterns):
        n = int(rng.integers(1, max_len))
        keys = rng.choice(n_entities, size=n, replace=False)
        scores = rng.random(n) * 10 + 0.1
        lists.append((keys, scores))
    return lists


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_star_join_cardinality_vs_numpy(seed):
    rng = np.random.default_rng(seed)
    lists = _random_lists(rng, 5)
    store = _store_from(lists)
    # Random query over a subset of patterns, including inactive tails.
    T = 4
    pids = rng.choice(5, size=T, replace=False)
    active = np.ones(T, bool)
    active[rng.integers(1, T):] = False     # suffix inactive (PAD convention)
    n = estimator.star_join_cardinality(
        store, jnp.asarray(pids, jnp.int32), jnp.asarray(active))
    expect = set(lists[pids[0]][0])
    for t in range(1, T):
        if active[t]:
            expect &= set(lists[pids[t]][0])
    assert float(n) == float(len(expect)), (pids, active)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_per_relaxation_cardinalities_vs_numpy(seed):
    """exact_cardinalities' (T, R) output == per-relaxation set algebra,
    with PAD-padded queries and a pattern that has zero relaxations."""
    rng = np.random.default_rng(seed + 100)
    lists = _random_lists(rng, 7)
    store = _store_from(lists)
    # Patterns 0..3 are query-able; 4..6 serve as relaxations. Pattern 1
    # gets no relaxations at all; others get 1-2.
    rules = {0: [(4, 0.8), (5, 0.4)], 2: [(6, 0.9)], 3: [(5, 0.7), (6, 0.3)]}
    relax = kg.build_relax_table(7, rules)
    R = relax.ids.shape[1]

    pattern_ids = np.asarray([0, 1, 2, int(PAD_KEY)], np.int32)  # padded T=4
    active = pattern_ids != int(PAD_KEY)
    n, n_rel = estimator.exact_cardinalities(
        store, relax, jnp.asarray(pattern_ids), jnp.asarray(active))

    key_sets = [set(k) for k, _ in lists]
    act = [t for t in range(4) if active[t]]
    expect_n = set.intersection(*[key_sets[pattern_ids[t]] for t in act])
    assert float(n) == float(len(expect_n))

    rel_ids = np.asarray(relax.ids)
    assert n_rel.shape == (4, R)
    for t in range(4):
        for r in range(R):
            got = float(n_rel[t, r])
            if not active[t]:
                # Inactive slots still evaluate with a safe pid; their
                # estimates are masked downstream — only shape matters.
                continue
            rid = rel_ids[pattern_ids[t], r]
            if rid < 0:
                assert got == 0.0, (t, r)
                continue
            parts = [key_sets[rid] if u == t else key_sets[pattern_ids[u]]
                     for u in act]
            assert got == float(len(set.intersection(*parts))), (t, r)


def test_zero_relaxation_pattern_has_neginf_estimates():
    """A pattern with no relaxations gets E_Q'(1) = -inf in every slot, so
    PLANGEN can never enable it."""
    rng = np.random.default_rng(7)
    lists = _random_lists(rng, 4)
    store = _store_from(lists)
    relax = kg.build_relax_table(4, {0: [(3, 0.9)]})   # pattern 1: none
    pattern_ids = jnp.asarray([0, 1], jnp.int32)
    active = jnp.asarray([True, True])
    _, e_q1 = estimator.query_score_estimates(
        store, relax, pattern_ids, active, 5, 128)
    assert e_q1.shape == (2, relax.ids.shape[1])
    assert np.all(np.asarray(e_q1)[1] == -np.inf)


# ---------------------------------------------------------------------------
# Key bitmaps: the popcount path equals the binary search and set algebra.
# ---------------------------------------------------------------------------

def _brute_counts(key_sets, rel_ids, pattern_ids, active):
    """(n, n_rel, n_join) of ``exact_cardinalities``/``joinable_counts`` by
    Python set algebra, for every slot (inactive patterns included, as the
    device functions compute them)."""
    T, R = len(pattern_ids), rel_ids.shape[1]
    safe = [p if p >= 0 else 0 for p in pattern_ids]
    act = [u for u in range(T) if active[u]]

    def inter(first, rest):
        out = set(first)
        for s in rest:
            out &= s
        return out

    def sources(u):
        return set().union(key_sets[safe[u]], *(
            key_sets[r] for r in rel_ids[safe[u]] if r >= 0))

    n = len(inter(key_sets[safe[0]], [key_sets[safe[u]] for u in act])
            ) if active[0] else 0
    n_rel = np.zeros((T, R))
    n_join = np.zeros((T, R))
    for t in range(T):
        for r in range(R):
            rid = rel_ids[safe[t], r]
            if rid < 0:
                continue
            others = [u for u in act if u != t]
            n_rel[t, r] = len(inter(key_sets[rid],
                                    [key_sets[safe[u]] for u in others]))
            n_join[t, r] = len(inter(key_sets[rid],
                                     [sources(u) for u in others]))
    return n, n_rel, n_join


@jax.jit
def _counts_and_plan(store, relax, pattern_ids, active):
    return (estimator.exact_cardinalities(store, relax, pattern_ids, active),
            estimator.joinable_counts(store, relax, pattern_ids, active),
            plangen.plan(store, relax, pattern_ids, 5, 64))


@pytest.mark.parametrize("T", [2, 3, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bitmap_counts_equal_binary_search(seed, T):
    """Counts from ``key_bits`` equal, as integers, the binary search's
    and the set algebra's, over padded pattern slots, PAD relaxation
    slots, inactive patterns, an empty list and keys at the word edges
    (0, 31, 32) and the domain's last id; the plans are the same bits."""
    rng = np.random.default_rng(seed)
    n_pat, domain, L = 12, 300, 48
    edge = np.asarray([0, 31, 32, domain - 1])
    lists = []
    for p in range(n_pat):
        n = int(rng.integers(8, L - len(edge)))
        keys = rng.choice(np.arange(33, domain - 1), size=n, replace=False)
        if p % 2 == 0:
            keys = np.concatenate([keys, edge])
        lists.append((keys, rng.random(len(keys)) * 10 + 0.1))
    lists[5] = (np.zeros(0, np.int32), np.zeros(0))          # empty list
    rules = {p: [(int(q), float(rng.uniform(0.2, 0.9)))
                 for q in rng.choice(n_pat, size=int(rng.integers(1, 4)),
                                     replace=False)]
             for p in range(n_pat) if p % 4 != 3}            # some have none
    relax = kg.build_relax_table(n_pat, rules, max_relax=4)  # PAD slots
    words = kg.bitmap_words(domain, L)
    assert words == 128
    bits = kg.build_store(lists, list_len=L, key_words=words)
    search = kg.build_store(lists, list_len=L, key_words=0)
    assert bits.key_bits.shape == (n_pat, words)
    assert search.key_bits.shape == (n_pat, 0)
    key_sets = [set(np.asarray(k).tolist()) for k, _ in lists]
    rel_ids = np.asarray(relax.ids)
    for trial in range(6):
        pids = rng.choice(n_pat, size=T, replace=False).astype(np.int32)
        if trial == 0:
            pids[0] = 5                                      # empty list
        n_act = T if trial < 2 else int(rng.integers(1, T + 1))
        pids[n_act:] = int(PAD_KEY)                          # padded slots
        active = pids != int(PAD_KEY)
        if trial == 5 and n_act > 1:
            active[n_act - 1] = False                        # inactive id
        args = (jnp.asarray(pids), jnp.asarray(active))
        (n_b, rel_b), join_b, plan_b = _counts_and_plan(bits, relax, *args)
        (n_s, rel_s), join_s, plan_s = _counts_and_plan(search, relax, *args)
        n_x, rel_x, join_x = _brute_counts(key_sets, rel_ids, pids, active)
        case = (pids, active)
        assert float(n_b) == float(n_s) == n_x, case
        np.testing.assert_array_equal(np.asarray(rel_b), np.asarray(rel_s))
        np.testing.assert_array_equal(np.asarray(rel_b), rel_x)
        np.testing.assert_array_equal(np.asarray(join_b), np.asarray(join_s))
        np.testing.assert_array_equal(np.asarray(join_b), join_x)
        np.testing.assert_array_equal(np.asarray(plan_b), np.asarray(plan_s))


def test_key_bits_layout():
    """Bit k % 32 of word k // 32 is set iff key k is in the list; keys
    beyond an explicit width are refused."""
    lists = [([0, 31, 32, 4095], [4, 3, 2, 1]), ([], []), ([7], [1])]
    store = kg.build_store([(np.asarray(k, np.int32), np.asarray(s, float))
                            for k, s in lists], key_words=128)
    bits = np.asarray(store.key_bits)
    assert bits.shape == (3, 128) and bits.dtype == np.uint32
    for (keys, _), row in zip(lists, bits):
        got = np.nonzero(np.unpackbits(row.view(np.uint8),
                                       bitorder="little"))[0]
        assert got.tolist() == sorted(keys)
    with pytest.raises(ValueError):
        kg.build_store([(np.asarray([4096], np.int32), np.ones(1))],
                       key_words=128)


def test_wide_key_domain_keeps_binary_search():
    """Where a bitmap row would outweigh a pattern's list data (domain
    beyond 96·L), the store carries a zero-width ``key_bits`` and the
    planner counts exactly as the binary search does."""
    L = 8
    assert kg.bitmap_words(96 * 1024, 1024) == 3072
    assert kg.bitmap_words(96 * 1024 + 1, 1024) == 0
    assert kg.bitmap_words(250_000, 8192) == 7936
    rng = np.random.default_rng(5)
    lists = [(rng.choice(97 * L * 32, size=L, replace=False),
              rng.random(L) + 0.1) for _ in range(4)]
    lists.append((np.asarray([2, 3, 96 * L * 32]), np.ones(3)))
    wide = _store_from(lists)
    assert wide.key_bits.shape == (5, 0)
    narrow = kg.build_store([(np.asarray(k, np.int32), s) for k, s in lists],
                            key_words=kg.bitmap_words(97 * L * 32, 10**6))
    relax = kg.build_relax_table(5, {0: [(4, 0.9)], 1: [(2, 0.7)]})
    for pids in ([0, 1, 3], [1, 0, 3], [0, 3, int(PAD_KEY)]):
        q = jnp.asarray(pids, jnp.int32)
        got = [_counts_and_plan(s, relax, q, q != PAD_KEY)
               for s in (wide, narrow)]
        for a, b in zip(*map(jax.tree_util.tree_leaves, got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
