"""Sketched cardinalities: bitmap key signatures for O(W) planner probes.

The exact planner (``estimator.exact_cardinalities``) answers every
"how many keys do these lists share" question by popcount over key
bitmaps as wide as the key domain, or, where the domain is too wide for
the lists, by binary searches over full posting lists, so its cost grows
with the domain or with L. This module trades a bounded relative error
for planning cost *independent of both* (DESIGN.md §6):

* **Ingest** — every pattern gets a fixed-width signature of ``LANES``
  independent bitmap lanes, each ``W`` uint32 words (m = 32·W bits). A key
  sets one bit per lane (a splitmix64-style mix keyed by the lane seed).
  Signatures are built host-side once, in ``kg.build_store`` — the sharded
  ingest inherits them per shard, so local estimates ``psum`` to global
  totals exactly like the exact counts.

* **Intersection cardinality** — AND the signatures and invert the
  occupancy model.  For sets of sizes ``n_t`` sharing ``x`` keys, a bit
  survives the T-way AND with probability

      pred(x) = (1 - e^{-x/m}) + e^{-x/m} · Π_t (1 - e^{-(n_t - x)/m})

  (the shared keys force common bits; residual keys only collide by
  chance).  ``pred`` is monotone in ``x``, so a short bisection recovers
  ``x`` from the observed AND fill — this bakes the collision correction
  in, so disjoint sets estimate ≈ 0 instead of the raw coincidental count.

* **Soundness of the zero** — a key contained in every set sets the same
  bit in every signature, so an empty AND in *any* lane proves the true
  intersection is empty; the estimators return exactly 0 in that case.
  Positive estimates are approximate, and the planner rounds sub-half-key
  *global* joinability estimates to 0 (``round_joinability``) — a bounded
  approximation of the exact dead-relaxation prune, lossy only at the
  0-vs-1-key knife edge that no sublinear sketch can split exactly.

Everything at query time is bitwise AND/OR + ``population_count`` over
``(LANES, W)`` words — O(T·R·W) per query instead of O(T·R·L·log L).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import TripleStore, RelaxTable, PAD_KEY

# Default signature geometry: 4 lanes × 1024 words = 32768 bits (4 KiB)
# per lane, 16 KiB per pattern. Sized so the dead-relaxation gate stays
# sharp on the benchmark workloads: the collision noise of an intersection
# estimate is ~sqrt(n_a·n_b / total_bits) keys, so 128 Ki total bits keeps
# it well under one key for lists up to ~500 keys joining source unions of
# a few thousand. Plan-time cost is O(W), independent of L, regardless.
#
# A calibration note on the zero gate: deciding set *disjointness* exactly
# needs Ω(n) bits (the communication lower bound), so any sketch narrower
# than the lists must sometimes report a small positive estimate for a
# truly empty intersection. We keep the zero *sound* (an empty AND lane
# proves emptiness; the occupancy model subtracts expected collision mass;
# sub-half-key joinability estimates round to 0) and size the default so
# the residual noise is far below one key at test/bench scales — at much
# longer L, widen ``words`` or accept a conservative (lossless) planner
# that occasionally keeps a dead relaxation.
SKETCH_LANES = 4
SKETCH_WORDS = 1024

# Adaptive sizing bounds: floor keeps tiny test stores statistically sane,
# the cap bounds signature bytes per pattern (16384 words = 64 KiB/lane).
MIN_WORDS = 128
MAX_WORDS = 16384


def adaptive_words(max_len: int) -> int:
    """Signature width (uint32 words per lane) sized from ingest stats.

    Sizing rule: m = 32·W ≥ 64·Lmax bits, i.e. W = 2·Lmax rounded up to a
    power of two. Rationale: linear counting and the AND-fill occupancy
    model both need the fill well below saturation — source unions run to
    ~(R+1)·Lmax keys, so 64 bits of budget per list item keeps worst-case
    union fill ≲ (R+1)/64 and the collision noise of intersection
    estimates (≈ sqrt(n_a·n_b / total_bits)) under a key at benchmark
    scales. The rule reproduces the historical fixed default at the
    benchmark geometry (Lmax = 512 → W = 1024) and widens automatically
    where the ROADMAP flagged saturation (posting lists ≫ 2k keys/lane).
    Power-of-two + clamped so shard geometries stay uniform and the jit
    cache stays small.
    """
    words = 2 * max(int(max_len), 1)
    words = 1 << max(words - 1, 1).bit_length()    # round up to pow2
    return int(min(max(words, MIN_WORDS), MAX_WORDS))


_FULL_WORD = np.uint32(0xFFFFFFFF)


def _mix64(x: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 finalizer (vectorized, uint64 wraparound)."""
    z = x.astype(np.uint64) + np.uint64(seed)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _lane_seed(lane: int) -> int:
    # Golden-ratio stepped seeds; independent of distributed.mix_hash's
    # multiplicative constant so shard-local key sets don't concentrate
    # on sketch bits.
    return (0x9E3779B97F4A7C15 * (lane + 1)) & 0xFFFFFFFFFFFFFFFF


def build_sketches(key_lists: list[np.ndarray],
                   lanes: int = SKETCH_LANES,
                   words: int = SKETCH_WORDS) -> np.ndarray:
    """Host-side ingest: (P, lanes, words) uint32 signatures of the key sets."""
    m = 32 * words
    out = np.zeros((len(key_lists), lanes, words), dtype=np.uint32)
    for p, keys in enumerate(key_lists):
        k = np.asarray(keys, np.uint64)
        if k.size == 0:
            continue
        for lane in range(lanes):
            bit = (_mix64(k, _lane_seed(lane)) % np.uint64(m)).astype(np.int64)
            word, off = bit >> 5, (bit & 31).astype(np.uint32)
            np.bitwise_or.at(out[p, lane], word,
                             np.uint32(1) << off)
    return out


# ---------------------------------------------------------------------------
# Device-side estimators (all jittable / vmappable).
# ---------------------------------------------------------------------------

def _lane_popcounts(bitmaps: jax.Array) -> jax.Array:
    """(..., LANES, W) uint32 → (..., LANES) f32 set-bit counts."""
    return jnp.sum(jax.lax.population_count(bitmaps), axis=-1).astype(
        jnp.float32)


def union_size(bitmaps: jax.Array, valid: jax.Array) -> jax.Array:
    """Linear-counting estimate of |∪_s S_s| from OR'd signatures.

    Args:
      bitmaps: (S, LANES, W) uint32; valid: (S,) bool (invalid rows skipped).
    Returns () f32.
    """
    m = jnp.float32(32 * bitmaps.shape[-1])
    union = jnp.bitwise_or.reduce(
        jnp.where(valid[:, None, None], bitmaps, jnp.uint32(0)), axis=0)
    fill = jnp.clip(_lane_popcounts(union) / m, 0.0, 1.0 - 1.0 / m)
    return jnp.mean(-m * jnp.log1p(-fill))


def intersection_size(bitmaps: jax.Array, sizes: jax.Array,
                      valid: jax.Array, iters: int = 26) -> jax.Array:
    """Estimate |∩_t S_t| over the valid rows by inverting the AND-fill model.

    Args:
      bitmaps: (T, LANES, W) uint32 signatures.
      sizes: (T,) f32 — |S_t| (exact where known, e.g. list lengths).
      valid: (T,) bool — rows to intersect.
    Returns () f32 ≥ 0; exactly 0 whenever any lane's AND is empty (which
    proves the true intersection is empty).
    """
    m = jnp.float32(32 * bitmaps.shape[-1])
    # AND-reduce via De Morgan (jnp.bitwise_and.reduce overflows on uint32).
    anded = ~jnp.bitwise_or.reduce(
        ~jnp.where(valid[:, None, None], bitmaps,
                   jnp.uint32(_FULL_WORD)), axis=0)     # (LANES, W)
    lane_pop = _lane_popcounts(anded)                    # (LANES,)
    y = jnp.mean(lane_pop) / m
    provably_empty = jnp.any(lane_pop == 0.0)

    sizes = jnp.where(valid, sizes, 0.0)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    hi0 = jnp.min(jnp.where(valid, sizes, jnp.inf))
    hi0 = jnp.where(jnp.isfinite(hi0), hi0, 0.0)

    def pred(x):
        u = jnp.exp(-x / m)
        a = 1.0 - jnp.exp(-jnp.maximum(sizes - x, 0.0) / m)
        return (1.0 - u) + u * jnp.prod(jnp.where(valid, a, 1.0))

    def step(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        below = pred(mid) < y
        return (jnp.where(below, mid, lo), jnp.where(below, hi, mid))

    lo, hi = jax.lax.fori_loop(0, iters, step, (jnp.float32(0.0), hi0))
    est = 0.5 * (lo + hi)
    # Degenerate arities: 0 valid sets → 0; 1 valid set → its exact size
    # (the AND-fill model is constant in x there, so the bisection is
    # uninformative — but the answer is known exactly).
    est = jnp.where(n_valid <= 1, jnp.sum(sizes), est)
    return jnp.where(provably_empty, 0.0, jnp.maximum(est, 0.0))


def sketch_cardinalities(store: TripleStore, relax: RelaxTable,
                         pattern_ids: jax.Array, active: jax.Array):
    """Sketched drop-in for ``estimator.exact_cardinalities``.

    Returns (n: (), n_rel: (T, R)) — original and per-relaxation join
    cardinality estimates. Local to the store it is given; under hash
    partitioning the per-shard estimates ``psum`` to the global estimate
    (key sets partition across shards, so the true counts are additive and
    each shard's estimator is unbiased for its share).
    """
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    safe_ids = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)
    sk = store.sketch[safe_ids]                          # (T, LANES, W)
    sizes = store.lengths[safe_ids].astype(jnp.float32)  # (T,)
    n = intersection_size(sk, sizes, active)

    def per_relaxation(t, r):
        rid = relax.ids[safe_ids[t], r]
        srid = jnp.where(rid == PAD_KEY, 0, rid)
        onehot = jnp.arange(T) == t
        bms = jnp.where(onehot[:, None, None], store.sketch[srid], sk)
        szs = jnp.where(onehot, store.lengths[srid].astype(jnp.float32),
                        sizes)
        est = intersection_size(bms, szs, active | onehot)
        return jnp.where(rid != PAD_KEY, est, 0.0)

    n_rel = jax.vmap(lambda t: jax.vmap(lambda r: per_relaxation(t, r))(
        jnp.arange(R)))(jnp.arange(T))
    return n, n_rel


def sketch_joinable_counts(store: TripleStore, relax: RelaxTable,
                           pattern_ids: jax.Array,
                           active: jax.Array) -> jax.Array:
    """Sketched drop-in for ``estimator.joinable_counts`` — (T, R) f32.

    Estimates, per relaxation, how many of its keys join the other active
    patterns' source unions. Returns exactly 0 when the sketch *proves*
    the count is 0 (any empty AND lane); otherwise the raw occupancy-model
    estimate, which can carry a sub-key collision residue for truly dead
    relaxations. Consumers that gate on ``> 0`` should round sub-half-key
    estimates to 0 via ``round_joinability`` — AFTER any cross-shard psum,
    so thinly-spread joinable mass is summed before the cut.
    """
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    safe_ids = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)

    rel_u = relax.ids[safe_ids]                          # (T, R)
    srcs = jnp.concatenate([safe_ids[:, None],
                            jnp.where(rel_u == PAD_KEY, 0, rel_u)], axis=1)
    src_ok = jnp.concatenate([jnp.ones((T, 1), bool),
                              rel_u != PAD_KEY], axis=1)  # (T, R+1)
    src_bm = store.sketch[srcs]                          # (T, R+1, LANES, W)
    union_bm = jnp.bitwise_or.reduce(
        jnp.where(src_ok[..., None, None], src_bm, jnp.uint32(0)), axis=1)
    union_sz = jax.vmap(
        lambda bm: union_size(bm[None], jnp.ones((1,), bool)))(union_bm)

    def per_relaxation(t, r):
        rid = relax.ids[safe_ids[t], r]
        srid = jnp.where(rid == PAD_KEY, 0, rid)
        onehot = jnp.arange(T) == t
        bms = jnp.where(onehot[:, None, None], store.sketch[srid], union_bm)
        szs = jnp.where(onehot, store.lengths[srid].astype(jnp.float32),
                        union_sz)
        est = intersection_size(bms, szs, active | onehot)
        return jnp.where(rid != PAD_KEY, est, 0.0)

    return jax.vmap(lambda t: jax.vmap(lambda r: per_relaxation(t, r))(
        jnp.arange(R)))(jnp.arange(T))


def round_joinability(est: jax.Array) -> jax.Array:
    """Zero out sub-half-key joinability estimates (the planner gates on
    ``> 0``). This is a *bounded approximation*, not a proof: it keeps
    chance collisions from resurrecting dead relaxations, at the price of
    occasionally zeroing a live relaxation whose estimated joinable mass
    is below half a key — so the sketch prune is slightly lossy at the
    0-vs-1-key knife edge (set disjointness needs Ω(n) bits; no narrow
    sketch can split it exactly). Exact mode remains the lossless oracle.
    Apply to the GLOBAL estimate (after psum in the distributed planner).
    """
    return jnp.where(est < 0.5, 0.0, est)
