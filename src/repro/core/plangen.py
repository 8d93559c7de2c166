"""PLANGEN (Algorithm 1): speculative selection of relaxations to process.

For each triple pattern q_i and each of its relaxations r the planner builds
the score distribution of the query with q_i replaced by that relaxation and
compares the expected best relaxed score E_Q'(1) with the expected k-th
score of the original query E_Q(k).

The returned plan is a ``(T, R)`` boolean mask — one bit per (pattern,
relaxation) pair. This generalizes the paper's per-pattern speculation
(which only probed the *top-weighted* relaxation and then dragged all R
siblings into the merge). The per-relaxation rule is two-stage:

1. *Whether* to relax pattern t: any of its relaxations has E_Q'(1) >
   E_Q(k) — the paper's speculation, hedged over all R candidates.
2. *Which* siblings ride along: a relaxation none of whose keys match
   every other pattern's union of sources cannot contribute to any answer
   (not even a multi-relaxed one), so it is masked out of the merge
   instead of feeding it dead items — a provably lossless prune.
   ``sibling_slack`` optionally tightens this to an E_Q'(1)-margin test
   for more aggressive (lossy) sibling pruning.

The executor is mask-parameterized, so TriniT is simply the all-True plan,
and the coarser per-pattern behavior is recoverable as
``per_pattern_plan(mask)`` (= ``mask.any(axis=1)`` broadcast over R).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import TripleStore, RelaxTable, PAD_KEY
from repro.core import estimator
from repro.core import scopes


def plan_from_estimates(e_qk: jax.Array, e_q1: jax.Array,
                        n_joinable: jax.Array, rel_exists: jax.Array,
                        active: jax.Array,
                        sibling_slack: float | None = None) -> jax.Array:
    """Build the (T, R) mask from (possibly psum'd) planner estimates.

    Args:
      e_qk: () expected k-th score of the original query.
      e_q1: (T, R) expected best score of each one-relaxation rewrite
        (-inf where the slot is padding or the pattern inactive).
      n_joinable: (T, R) counts of each relaxation's joinable keys
        (``estimator.joinable_counts``); zero ⇒ provably dead relaxation.
      rel_exists: (T, R) bool — relaxation slot is real (not PAD).
      active: (T,) bool — pattern is part of the query.
      sibling_slack: None keeps every joinable sibling of a speculated
        pattern. A float s ≥ 0 additionally requires
        ``E_Q'(1) ≥ E_Q(k) − s·(best_sibling − E_Q(k))`` — s=0 is the
        aggressive pure per-relaxation threshold, larger s is safer.
    """
    with jax.named_scope(scopes.ESTIMATE):
        promising = e_q1 > e_qk                           # (T, R)
        speculate = promising.any(axis=1, keepdims=True) & active[:, None]
        mask = speculate & (n_joinable > 0) & rel_exists
        if sibling_slack is not None:
            best = jnp.max(jnp.where(jnp.isfinite(e_q1), e_q1, -jnp.inf),
                           axis=1, keepdims=True)
            mask &= e_q1 >= e_qk - sibling_slack * (best - e_qk)
        return mask


def plan(store: TripleStore, relax: RelaxTable, pattern_ids: jax.Array,
         k: int, G: int = 512,
         sibling_slack: float | None = None,
         cardinality_mode: str = "exact") -> jax.Array:
    """Generate the speculative plan for one star query.

    Args:
      pattern_ids: (T,) int32 pattern ids (PAD_KEY padded for shorter queries).
      k: top-k target (static).
      G: histogram grid bins per unit score (static).
      sibling_slack: see ``plan_from_estimates``.
      cardinality_mode: "exact" (true selectivities: popcount over the
        store's key bitmaps, or binary search where it has none) or
        "sketch" (bitmap-signature estimates, L-independent).

    Returns:
      (T, R) bool — True where relaxation r of pattern t must be processed.
      Rows of padded patterns and padded relaxation slots are always False.
    """
    active = pattern_ids != PAD_KEY
    e_qk, e_q1 = estimator.query_score_estimates(
        store, relax, pattern_ids, active, k, G, cardinality_mode)
    n_joinable = estimator.joinability(store, relax, pattern_ids, active,
                                       cardinality_mode)
    if cardinality_mode == "sketch":
        from repro.core import sketches
        n_joinable = sketches.round_joinability(n_joinable)
    safe_ids = jnp.where(active, pattern_ids, 0)
    rel_exists = relax.ids[safe_ids] != PAD_KEY
    return plan_from_estimates(e_qk, e_q1, n_joinable, rel_exists, active,
                               sibling_slack)


def per_pattern_plan(mask: jax.Array) -> jax.Array:
    """Coarsen a (T, R) plan to per-pattern granularity.

    A pattern with *any* promising relaxation processes *all* of them — the
    paper's original speculation granularity, kept as an ablation baseline.
    """
    return jnp.broadcast_to(mask.any(axis=1, keepdims=True), mask.shape)


def trinit_plan(pattern_ids: jax.Array, n_relax: int) -> jax.Array:
    """The non-speculative baseline: every relaxation of every pattern is
    processed. Returns the all-True (T, R) mask (False on padded patterns)."""
    active = pattern_ids != PAD_KEY
    return jnp.broadcast_to(active[:, None],
                            (pattern_ids.shape[0], n_relax))
