"""Core pytree types for the Spec-QP engine.

All arrays are dense, fixed-shape, device-resident. Lists are sorted by
score (descending) and padded: keys with ``PAD_KEY`` (=-1), scores with 0.

Shapes use the following symbols:
  P  — number of triple patterns known to the store
  L  — max posting-list length (padded)
  R  — max relaxations per pattern
  T  — number of triple patterns in a query (static per jit specialization)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

PAD_KEY = jnp.int32(-1)
# Sentinel used in *key-sorted* arrays so padding sorts to the end.
KEY_SENTINEL = jnp.int32(2**31 - 1)
NEG_INF = jnp.float32(-jnp.inf)


def _pytree(cls):
    """Register a dataclass as a JAX pytree (all fields are children)."""
    cls = dataclasses.dataclass(frozen=True)(cls)
    fields = [f.name for f in dataclasses.fields(cls)]

    def flatten(obj):
        return [getattr(obj, n) for n in fields], None

    def unflatten(_, children):
        return cls(*children)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


@_pytree
class TripleStore:
    """Scored posting lists for every triple pattern in the KG.

    ``keys``/``scores`` are sorted by score desc per pattern. ``scores`` are
    normalized per Definition 5 (divided by the pattern's max raw score), so
    every non-empty pattern's top score is exactly 1.0.
    ``sorted_keys`` is the same key set sorted ascending by key (padding →
    KEY_SENTINEL) for O(log L) membership probes.
    ``stats`` holds the paper's four per-pattern statistics
    ``(m, sigma_r, S_r, S_m)`` (§3.1.1).
    ``sketch`` holds fixed-width bitmap key signatures (DESIGN.md §6) for
    the sketched cardinality planner; its width is independent of L.
    ``key_bits`` holds each pattern's exact key set as a bitmap over the key
    domain: bit ``k % 32`` of word ``k // 32`` is set iff key ``k`` is in
    the list. The exact planner counts by AND/OR and popcount over it; it
    is zero-width (P, 0) where the domain is too wide for the lists
    (``kg.bitmap_words``), and the planner then binary-searches
    ``sorted_keys``.
    """

    keys: jax.Array          # (P, L) int32, PAD_KEY padded
    scores: jax.Array        # (P, L) f32 in [0, 1], 0 padded
    lengths: jax.Array       # (P,)  int32
    sorted_keys: jax.Array   # (P, L) int32 ascending, KEY_SENTINEL padded
    stats: jax.Array         # (P, 4) f32: m, sigma_r, S_r, S_m
    sketch: jax.Array        # (P, LANES, W) uint32 bitmap signatures
    key_bits: jax.Array      # (P, Wk) uint32 exact key bitmaps, Wk may be 0


@_pytree
class RelaxTable:
    """Weighted relaxation rules r = (q, q', w), grouped by domain pattern.

    Relaxations are sorted by weight desc. The paper only ever *plans* with
    the top-weighted one (§3.2.1); our planner generalizes this and emits a
    per-relaxation (T, R) decision, so every slot is estimated.
    """

    ids: jax.Array       # (P, R) int32 pattern ids, PAD_KEY padded
    weights: jax.Array   # (P, R) f32 in [0, 1], 0 padded


@_pytree
class EngineResult:
    """Top-k answers plus the paper's efficiency counters."""

    keys: jax.Array        # (k,) int32, PAD_KEY padded
    scores: jax.Array      # (k,) f32, -inf padded
    n_pulled: jax.Array    # () int32 — items materialized from input lists
    n_answers: jax.Array   # () int32 — (partial) answer objects created
    n_iters: jax.Array     # () int32 — while-loop trips doing real work
    n_wasted: jax.Array    # () int32 — lockstep trips spent idle after
                           # this lane finished (0 for single queries;
                           # see engine._execute_refill / DESIGN.md §8)
    relax_mask: jax.Array  # (T, R) bool — which relaxation sources joined
                           # the merge (the plan; all-True for TriniT). The
                           # per-pattern view is relax_mask.any(axis=1).


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine hyper-parameters (hashable; part of jit static args)."""

    block: int = 64           # items pulled per merge step
    k: int = 10               # top-k
    grid_bins: int = 512      # histogram grid resolution per unit score
    # Sibling-pruning aggressiveness of the (T, R) planner: None keeps every
    # joinable relaxation of a speculated pattern; a float s adds the
    # E_Q'(1) margin test (0 = most aggressive). See plangen.plan.
    plan_slack: float | None = None
    # How the planner prices joins: "exact" counts the true intersections
    # (the paper's footnote-3 oracle) by popcount over the store's key
    # bitmaps, or by binary search where the store has none; "sketch" uses
    # the bitmap signatures (O(W) per probe, L-independent — see
    # sketches.py / DESIGN.md §6).
    cardinality_mode: str = "exact"
    # Probe seen rings with the Pallas rank-join kernel: compiled on a TPU,
    # run by the Pallas interpreter anywhere else (kernels/ops.py).
    use_pallas: bool = False
    # Cap on the per-stream seen buffer (None = worst-case R1·L sizing).
    # The executor rounds the cap up to a whole number of blocks so the
    # ring wraps block-aligned (see engine._seen_size).
    # Rank joins terminate long before worst case in practice; the cap
    # bounds the probe bytes per iteration (§Perf on the kg-specqp cell).
    # Overflowing the cap wraps the ring (answers pulled that deep may be
    # missed) — the executor reports max fill via n_answers accounting and
    # benchmarks validate no quality loss at the chosen cap.
    seen_cap: int | None = None


def tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
