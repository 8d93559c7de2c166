"""Distributed Spec-QP: hash-partitioned KG shards under ``shard_map``.

Scale-out story (DESIGN.md §5): partition the KG by a mixing hash of the
*join key* so that a key's triples for every pattern land on one shard.
Star joins then decompose exactly:

  global top-k  =  top-k( ∪_shards local top-k )
  global |∩ K_t| = Σ_shards local |∩ K_t|        (cardinalities psum)

Each device runs the full planner + executor on its partition; the plan is
identical everywhere because it only consumes the replicated global stats
table and psum'd cardinalities. One ``all_gather`` of (k,) buffers merges
results — the DRJN pattern mapped onto jax collectives. On the production
mesh the gather runs over the flattened (pod, data, model) axes, i.e. a
two-level tree (intra-pod reduce then cross-pod) as lowered by XLA.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.types import (TripleStore, RelaxTable, EngineResult,
                              EngineConfig, PAD_KEY)
from repro.core import kg as kglib
from repro.core import sketches as sketchlib
from repro.core import engine, estimator, histogram, plangen


def mix_hash(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Cheap multiplicative mixing hash → shard id (avoids range artifacts)."""
    h = (keys.astype(np.uint64) * np.uint64(2654435761)) % np.uint64(2**32)
    return (h % np.uint64(n_shards)).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ShardedKG:
    """Host-built sharded store: leading axis = shard."""

    stores: TripleStore       # every field has a leading (S,) axis
    relax: RelaxTable         # replicated
    global_stats: jax.Array   # (P, 4) — stats of the *unsharded* lists
    n_shards: int


def shard_workload(pattern_lists, n_shards: int,
                   list_len: int | None = None
                   ) -> tuple[TripleStore, np.ndarray]:
    """Partition per-pattern (keys, raw_scores) lists into S shard stores.

    Scores are normalized by the GLOBAL per-pattern max before sharding
    (Definition 5 is a global property), and the global two-bucket stats are
    computed on the full lists; shard stores keep their local lists sorted.
    Returns host (numpy) arrays: the shard stores stacked on a leading
    (S,) axis and the (P, 4) global stats.
    """
    P_n = len(pattern_lists)
    norm_lists = []
    g_stats = np.zeros((P_n, 4), np.float32)
    shard_ids = []
    for p, (k, s) in enumerate(pattern_lists):
        k = np.asarray(k, np.int64)
        s = np.asarray(s, np.float64)
        mx = s.max() if len(s) else 1.0
        sn = s / mx if mx > 0 else s
        order = np.argsort(-sn, kind="stable")
        g_stats[p] = kglib.compute_pattern_stats(
            sn[order].astype(np.float32), len(k))
        norm_lists.append((k, sn))
        shard_ids.append(mix_hash(k, n_shards) if len(k) else
                         np.zeros((0,), np.int64))

    if list_len is None:
        # True per-shard maximum, not a mean-based heuristic: under hash
        # imbalance a hot shard can exceed 2x-mean-style margins and trip
        # build_store's length assert.
        list_len = 1
        for sid in shard_ids:
            if len(sid):
                list_len = max(list_len,
                               int(np.bincount(sid,
                                               minlength=n_shards).max()))

    # One signature geometry for every shard, sized from the GLOBAL longest
    # list: shard stores stack into a single (S, P, ...) pytree and their
    # sketch estimates psum, so per-shard adaptive widths (which would
    # differ under hash skew) are not an option here.
    longest = max((len(k) for k, _ in pattern_lists), default=1)
    sketch_words = sketchlib.adaptive_words(longest)
    # Likewise one key-bitmap width, the one the unsharded lists get: keys
    # keep their global ids on every shard.
    key_words = kglib.key_words_for([k for k, _ in pattern_lists], longest)
    shard_stores = []
    for s_id in range(n_shards):
        per_pattern = []
        for (k, sn), sid in zip(norm_lists, shard_ids):
            sel = sid == s_id
            per_pattern.append((k[sel].astype(np.int32), sn[sel]))
        shard_stores.append(kglib.build_store_host(
            per_pattern, list_len=list_len, normalize=False,
            sketch_words=sketch_words, key_words=key_words))

    stores = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *shard_stores)
    return stores, g_stats


def build_sharded_kg(pattern_lists, relax: RelaxTable,
                     n_shards: int, list_len: int | None = None,
                     mesh: jax.sharding.Mesh | None = None) -> ShardedKG:
    """Hash-partition the lists into ``n_shards`` stores, placed once.

    With a ``mesh`` each shard goes straight to its own device (sharded
    over all mesh axes) and the relaxation table and global stats are
    replicated; without one everything lands on the default device.
    """
    stores, g_stats = shard_workload(pattern_lists, n_shards, list_len)
    sh = rep = None
    if mesh is not None:
        sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        rep = NamedSharding(mesh, P())
    return ShardedKG(stores=jax.device_put(stores, sh),
                     relax=jax.device_put(relax, rep),
                     global_stats=jax.device_put(g_stats, rep),
                     n_shards=n_shards)


def _shard_body(store: TripleStore, relax: RelaxTable,
                global_stats: jax.Array, pattern_ids: jax.Array,
                cfg: EngineConfig, mode: str, axis_names: tuple[str, ...]):
    """Runs on one device under shard_map: plan globally, execute locally."""
    active = pattern_ids != PAD_KEY
    R = relax.ids.shape[1]
    if mode == "trinit":
        mask = plangen.trinit_plan(pattern_ids, R)
    elif mode in ("specqp", "specqp_pattern"):
        # Local cardinalities psum to global totals under hash partitioning
        # for both flavors: key sets partition across shards, so exact
        # counts are additive, and the sketch estimates (built from
        # shard-local signatures at ingest) are additive in expectation.
        n_loc, n_rel_loc = estimator.cardinalities(
            store, relax, pattern_ids, active, cfg.cardinality_mode)
        n = n_loc
        n_rel = n_rel_loc                    # (T, R)
        n_join = estimator.joinability(store, relax, pattern_ids, active,
                                       cfg.cardinality_mode)
        for ax in axis_names:
            n = jax.lax.psum(n, ax)
            n_rel = jax.lax.psum(n_rel, ax)
            n_join = jax.lax.psum(n_join, ax)
        if cfg.cardinality_mode == "sketch":
            # Round the GLOBAL estimate: joinable mass spread thinly
            # across shards must be summed before the sub-key cut.
            from repro.core import sketches
            n_join = sketches.round_joinability(n_join)
        e_qk, e_q1 = estimator.score_estimates_from_cards(
            global_stats, relax, pattern_ids, active, n, n_rel,
            cfg.k, cfg.grid_bins)
        safe_ids = jnp.where(active, pattern_ids, 0)
        rel_exists = relax.ids[safe_ids] != PAD_KEY
        mask = plangen.plan_from_estimates(
            e_qk, e_q1, n_join, rel_exists, active, cfg.plan_slack)
        if mode == "specqp_pattern":
            mask = plangen.per_pattern_plan(mask)
    elif mode == "join_only":
        mask = jnp.zeros((pattern_ids.shape[0], R), dtype=bool)
    else:
        raise ValueError(mode)

    # Local execution routes through the unified executor (the same
    # _step loop as every host entry point) in its single-query
    # degenerate configuration: depth-1 queue on one lane.
    local = engine.execute_queue(store, relax, pattern_ids[None],
                                 mask[None], cfg, lanes=1)

    # Two-level merge of local top-k buffers.
    keys, scores = local.keys[0], local.scores[0]
    for ax in axis_names:
        keys = jax.lax.all_gather(keys, ax).reshape(-1)
        scores = jax.lax.all_gather(scores, ax).reshape(-1)
        scores, idx = jax.lax.top_k(scores, cfg.k)
        keys = keys[idx]
    n_pulled = local.n_pulled[0]
    n_answers = local.n_answers[0]
    n_iters = local.n_iters[0]
    for ax in axis_names:
        n_pulled = jax.lax.psum(n_pulled, ax)
        n_answers = jax.lax.psum(n_answers, ax)
        n_iters = jax.lax.pmax(n_iters, ax)
    return EngineResult(keys=keys, scores=scores, n_pulled=n_pulled,
                        n_answers=n_answers, n_iters=n_iters,
                        n_wasted=local.n_wasted[0], relax_mask=mask)


def _shard_mapped(body, mesh: jax.sharding.Mesh,
                  shard_axes: tuple[str, ...]):
    """shard_map ``body(local_store, relax, gstats, queries)`` over the
    stacked shard axis; relax, stats and queries are replicated."""
    rep = P()

    def call(stores, relax, gstats, queries):
        # Each field of `stores` is (S, P, ...) sharded on axis 0 → the
        # body sees (1, P, ...); index the unit shard axis away.
        def local(stores, relax, gstats, queries):
            return body(jax.tree_util.tree_map(lambda x: x[0], stores),
                        relax, gstats, queries)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(shard_axes), stores),
                      jax.tree_util.tree_map(lambda _: rep, relax),
                      rep, rep),
            out_specs=EngineResult(keys=rep, scores=rep, n_pulled=rep,
                                   n_answers=rep, n_iters=rep, n_wasted=rep,
                                   relax_mask=rep),
            check_vma=False,
        )(stores, relax, gstats, queries)

    return call


@partial(jax.jit, static_argnames=("cfg", "mode", "mesh", "shard_axes"))
def _run_sharded(stores, relax, gstats, pattern_ids, cfg: EngineConfig,
                 mode: str, mesh: jax.sharding.Mesh,
                 shard_axes: tuple[str, ...]) -> EngineResult:
    return _shard_mapped(
        lambda st, rl, gs, q: _shard_body(st, rl, gs, q, cfg, mode,
                                          shard_axes),
        mesh, shard_axes)(stores, relax, gstats, pattern_ids)


def run_query_sharded(skg: ShardedKG, pattern_ids: jax.Array,
                      cfg: EngineConfig, mode: str, mesh: jax.sharding.Mesh,
                      shard_axes: tuple[str, ...] | None = None
                      ) -> EngineResult:
    """Answer one star query over a hash-partitioned KG on ``mesh``.

    ``shard_axes`` — mesh axes the store is partitioned over (all, default).
    """
    shard_axes = shard_axes or tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in shard_axes]))
    assert skg.n_shards == n_dev, (skg.n_shards, n_dev)
    return _run_sharded(skg.stores, skg.relax, skg.global_stats,
                        pattern_ids, cfg=cfg, mode=mode, mesh=mesh,
                        shard_axes=shard_axes)


def make_batched_sharded_fn(cfg: EngineConfig, mode: str,
                            mesh: jax.sharding.Mesh,
                            shard_axes: tuple[str, ...] | None = None):
    """Build jit(fn(stores, relax, gstats, queries (B,T))) → EngineResult.

    This is the production serve_step the dry-run lowers: every device runs
    the planner + executor on its KG partition for the whole query batch
    (vmap), then the per-axis gather/top-k tree merges results.
    """
    shard_axes = shard_axes or tuple(mesh.axis_names)

    def body(local, relax, gstats, queries):
        return jax.vmap(lambda q: _shard_body(local, relax, gstats, q, cfg,
                                              mode, shard_axes))(queries)

    return jax.jit(_shard_mapped(body, mesh, shard_axes))
