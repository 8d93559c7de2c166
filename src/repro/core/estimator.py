"""Expected-score estimator (§3.1): join cardinalities + order statistics.

Cardinalities come in two interchangeable flavors behind the
``cardinality_mode`` knob (``cardinalities`` / ``joinability`` dispatch):

* ``"exact"`` — exact join selectivities like the paper (footnote 3): for
  star joins on a shared variable the join cardinality is the size of the
  intersection of the per-pattern key sets, computed with vectorized
  binary searches over the key-sorted copies kept in the store
  (O(L log L) per probe).
* ``"sketch"`` — bitmap-signature estimates (sketches.py, DESIGN.md §6):
  O(W) bitwise popcounts per probe, planning cost independent of L.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import TripleStore, RelaxTable, PAD_KEY, KEY_SENTINEL
from repro.core import histogram
from repro.core import scopes
from repro.core import sketches


def member(sorted_keys: jax.Array, probes: jax.Array) -> jax.Array:
    """probes ∈ sorted_keys (ascending, KEY_SENTINEL padded) → (N,) bool."""
    idx = jnp.searchsorted(sorted_keys, probes, side="left")
    idx = jnp.clip(idx, 0, sorted_keys.shape[0] - 1)
    found = sorted_keys[idx] == probes
    return found & (probes != PAD_KEY) & (probes != KEY_SENTINEL)


def star_join_cardinality(store: TripleStore, pattern_ids: jax.Array,
                          active: jax.Array) -> jax.Array:
    """|∩_t keys(q_t)| over the active patterns of a star query.

    pattern_ids: (T,) int32 (entries with active=False ignored).
    Returns () f32 cardinality.
    """
    base_id = pattern_ids[0]
    base_keys = store.keys[base_id]          # (L,) score-ordered; any order ok
    valid = base_keys != PAD_KEY

    def body(mask, t):
        pid = pattern_ids[t]
        m = member(store.sorted_keys[pid], base_keys)
        return jnp.where(active[t], mask & m, mask), None

    T = pattern_ids.shape[0]
    mask, _ = jax.lax.scan(body, valid, jnp.arange(1, T))
    mask = mask & jnp.where(active[0], True, False)  # active[0] always True by convention
    return jnp.sum(mask.astype(jnp.float32))


def relaxed_join_cardinality(store: TripleStore, pattern_ids: jax.Array,
                             active: jax.Array, t_relax: jax.Array,
                             relax_id: jax.Array) -> jax.Array:
    """Cardinality of the query with pattern ``t_relax`` replaced by ``relax_id``.

    Uses the relaxed list as the probe base so the swap works for any t.
    """
    base_keys = store.keys[relax_id]
    valid = base_keys != PAD_KEY

    def body(mask, t):
        pid = pattern_ids[t]
        m = member(store.sorted_keys[pid], base_keys)
        skip = (t == t_relax) | ~active[t]
        return jnp.where(skip, mask, mask & m), None

    T = pattern_ids.shape[0]
    mask, _ = jax.lax.scan(body, valid, jnp.arange(T))
    has_relax = relax_id != PAD_KEY
    return jnp.where(has_relax, jnp.sum(mask.astype(jnp.float32)), 0.0)


def joinable_counts(store: TripleStore, relax: RelaxTable,
                    pattern_ids: jax.Array, active: jax.Array) -> jax.Array:
    """(T, R) f32 — per relaxation, how many of its keys can join at all.

    A key of relaxation r (of pattern t) is *joinable* if every other
    active pattern u matches it on the union of u's sources (original ∪
    all relaxations). A zero count proves relaxation r cannot contribute
    to any answer — not even a multi-relaxed one — so the planner may mask
    it without any loss. Local counts ``psum`` to global under hash
    partitioning, like the exact cardinalities.
    """
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    safe_ids = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)

    def member_union(u_pid, probes):
        rel_u = relax.ids[u_pid]                       # (R,)
        srcs = jnp.concatenate([u_pid[None],
                                jnp.where(rel_u == PAD_KEY, 0, rel_u)])
        valid = jnp.concatenate([jnp.ones((1,), bool), rel_u != PAD_KEY])
        m = jax.vmap(lambda s: member(store.sorted_keys[s], probes))(srcs)
        return jnp.any(m & valid[:, None], axis=0)

    def per_relaxation(t, r):
        rid = relax.ids[safe_ids[t], r]
        base = store.keys[jnp.where(rid == PAD_KEY, 0, rid)]
        ok = base != PAD_KEY

        def body(mask, u):
            skip = (u == t) | ~active[u]
            m = member_union(safe_ids[u], base)
            return jnp.where(skip, mask, mask & m), None

        mask, _ = jax.lax.scan(body, ok, jnp.arange(T))
        return jnp.where(rid != PAD_KEY,
                         jnp.sum(mask.astype(jnp.float32)), 0.0)

    return jax.vmap(lambda t: jax.vmap(lambda r: per_relaxation(t, r))(
        jnp.arange(R)))(jnp.arange(T))


def exact_cardinalities(store: TripleStore, relax: RelaxTable,
                        pattern_ids: jax.Array, active: jax.Array):
    """(n, n_rel (T, R)) — original and per-relaxation join cardinalities.

    ``n_rel[t, r]`` is the cardinality of the query with pattern ``t``
    replaced by its r-th relaxation (0 where the relaxation slot is padding).
    Purely local to the store it is given; under hash partitioning the
    global cardinality is the ``psum`` of per-shard values (a key's triples
    for every pattern live on one shard).
    """
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    safe_ids = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)
    n = star_join_cardinality(store, safe_ids, active)

    def per_relaxation(t, r):
        pid = safe_ids[t]
        rid = relax.ids[pid, r]
        return relaxed_join_cardinality(store, safe_ids, active, t, rid)

    n_rel = jax.vmap(lambda t: jax.vmap(lambda r: per_relaxation(t, r))(
        jnp.arange(R)))(jnp.arange(T))
    return n, n_rel


def cardinalities(store: TripleStore, relax: RelaxTable,
                  pattern_ids: jax.Array, active: jax.Array,
                  mode: str = "exact"):
    """(n, n_rel) join cardinalities under ``mode`` ∈ {"exact", "sketch"}.

    Both flavors are local to the store they are given and ``psum`` to
    global values under hash partitioning.
    """
    with jax.named_scope(scopes.CARDINALITY):
        if mode == "exact":
            return exact_cardinalities(store, relax, pattern_ids, active)
        if mode == "sketch":
            return sketches.sketch_cardinalities(store, relax, pattern_ids,
                                                 active)
    raise ValueError(f"unknown cardinality_mode: {mode!r}")


def joinability(store: TripleStore, relax: RelaxTable,
                pattern_ids: jax.Array, active: jax.Array,
                mode: str = "exact") -> jax.Array:
    """(T, R) joinable-key counts under ``mode`` ∈ {"exact", "sketch"}.

    The sketch flavor's zeros are sound (an empty AND lane proves
    emptiness) but its positives are estimates; the planner additionally
    rounds sub-half-key global estimates to 0 (``sketches.
    round_joinability``), a bounded approximation of the exact prune.
    """
    with jax.named_scope(scopes.JOINABILITY):
        if mode == "exact":
            return joinable_counts(store, relax, pattern_ids, active)
        if mode == "sketch":
            return sketches.sketch_joinable_counts(store, relax, pattern_ids,
                                                   active)
    raise ValueError(f"unknown cardinality_mode: {mode!r}")


def leave_one_out_pmfs(pmfs: jax.Array, active: jax.Array) -> jax.Array:
    """loo[t] = convolution of every *active* pattern pmf except pattern t.

    Computed with prefix/suffix convolution scans so swapping any pattern's
    pmf costs one extra convolution instead of T — the planner evaluates
    T·R relaxed queries, so this turns O(T²·R) convolutions into O(T + T·R).

    Args:
      pmfs: (T, G+1) per-pattern pmfs on [0, 1].
      active: (T,) bool.
    Returns: (T, T*G+1) unnormalized leave-one-out pmfs on [0, T].
    """
    T, G1 = pmfs.shape
    G = G1 - 1
    out_len = T * G + 1
    delta = jnp.zeros((out_len,), jnp.float32).at[0].set(1.0)

    def step(acc, xs):
        pmf, act = xs
        nxt = jnp.where(act, histogram.conv_truncate(acc, pmf, out_len), acc)
        return nxt, acc      # emit acc BEFORE folding in this pattern

    _, prefix = jax.lax.scan(step, delta, (pmfs, active))
    _, suffix_rev = jax.lax.scan(step, delta, (pmfs[::-1], active[::-1]))
    suffix = suffix_rev[::-1]
    return jax.vmap(
        lambda p, s: histogram.conv_truncate(p, s, out_len))(prefix, suffix)


def score_estimates_from_cards(stats_table: jax.Array, relax: RelaxTable,
                               pattern_ids: jax.Array, active: jax.Array,
                               n: jax.Array, n_rel: jax.Array,
                               k: int, G: int):
    """E_Q(k) and per-relaxation E_Q'(1) given (possibly psum'd) cardinalities.

    ``n_rel`` is (T, R); the returned ``e_q1`` is (T, R) with -inf where the
    relaxation slot is padding or the pattern is inactive.
    ``stats_table`` is the *global* (P, 4) statistics array — tiny and
    replicated in the distributed engine.
    """
    with jax.named_scope(scopes.ESTIMATE):
        T = pattern_ids.shape[0]
        R = relax.ids.shape[1]
        safe_ids = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)
        stats = stats_table[safe_ids]                      # (T, 4)
        pmfs = jax.vmap(lambda s: histogram.pattern_pmf(s, 1.0, G))(stats)

        pmf_q = histogram.convolve_pmfs(pmfs, active)
        e_qk = histogram.expected_order_statistic(pmf_q, n, jnp.float32(k), G)

        loo = leave_one_out_pmfs(pmfs, active)             # (T, T*G+1)
        out_len = loo.shape[1]

        def per_relaxation(t, r):
            pid = safe_ids[t]
            rid = relax.ids[pid, r]
            w = relax.weights[pid, r]
            safe_rid = jnp.where(rid == PAD_KEY, 0, rid)
            relaxed_pmf = histogram.pattern_pmf(stats_table[safe_rid], w, G)
            pmf_qr = histogram.conv_truncate(loo[t], relaxed_pmf, out_len)
            pmf_qr = pmf_qr / jnp.maximum(jnp.sum(pmf_qr), 1e-30)
            e1 = histogram.expected_order_statistic(
                pmf_qr, n_rel[t, r], jnp.float32(1.0), G)
            usable = (rid != PAD_KEY) & active[t]
            return jnp.where(usable, e1, -jnp.inf)

        e_q1 = jax.vmap(lambda t: jax.vmap(lambda r: per_relaxation(t, r))(
            jnp.arange(R)))(jnp.arange(T))
        return e_qk, e_q1


def query_score_estimates(store: TripleStore, relax: RelaxTable,
                          pattern_ids: jax.Array, active: jax.Array,
                          k: int, G: int, cardinality_mode: str = "exact"):
    """E_Q(k) for the original query and E_Q'(1) for every relaxed query.

    Returns (e_qk: (), e_q1: (T, R)) — the quantities PLANGEN compares,
    one estimate per (pattern, relaxation) pair.
    """
    n, n_rel = cardinalities(store, relax, pattern_ids, active,
                             cardinality_mode)
    return score_estimates_from_cards(
        store.stats, relax, pattern_ids, active, n, n_rel, k, G)
