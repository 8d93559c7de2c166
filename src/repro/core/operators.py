"""Vectorized TriniT operators: Incremental Merge and (n-ary) Rank Join.

TPU-native redesign of the paper's pull-based iterators (DESIGN.md §2):

* Incremental Merge — a *blockwise* pull: the next ``B`` items of the merged
  (weight-scaled, score-desc) stream are the top-B of the union of every
  source list's next-B window. One ``top_k`` per pull instead of B heap pops.

* Rank Join — block-nested: each pulled block is equi-joined against the
  other streams' *seen* buffers with an equality-contraction that is shaped
  exactly like an attention QKᵀ tile (the Pallas kernel `rank_join` targets
  it on TPU; the jnp path below is the oracle/CPU fallback).

Keys are unique within every source list (an entity matches a pattern once),
and pulled blocks are deduplicated against their own stream's history, so
seen buffers hold unique keys — the sum-contraction lookup is exact.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import PAD_KEY, NEG_INF
from repro.kernels import ref as kref


def lookup_scores(seen_keys: jax.Array, seen_scores: jax.Array,
                  probe_keys: jax.Array, seen_cnt: jax.Array,
                  use_pallas: bool = False):
    """Probe ``probe_keys`` (B,) against a unique-key buffer (N,).

    Returns (scores (B,) f32 with 0 where missing, found (B,) bool).
    ``use_pallas`` routes the probe to the rank-join kernel, which the
    platform decides how to run (``kernels.ops``); otherwise the kernel's
    jnp oracle probes the buffer, in tiles when it is long.

    Live window: slots written at least once. seen_cnt counts appended
    items cumulatively; once the ring wraps (seen_cnt >= N) every slot
    holds current data — ring alignment (N a multiple of the block) in
    the engine guarantees wrapped appends replace whole stale blocks, so
    "written" == "live" and no half-overwritten fragment survives.
    """
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.rank_join_lookup(seen_keys, seen_scores, probe_keys,
                                     seen_cnt, impl="pallas")
    n = seen_keys.shape[0]
    tile = 4096
    if n <= tile:
        return kref.rank_join_lookup_ref(seen_keys, seen_scores, probe_keys,
                                         seen_cnt)
    # Tiled scan mirrors the Pallas kernel's streaming: transient memory is
    # B×tile instead of B×N (matters for the production-scale KG cells).
    pad = -n % tile
    if pad:
        seen_keys = jnp.pad(seen_keys, (0, pad), constant_values=PAD_KEY)
        seen_scores = jnp.pad(seen_scores, (0, pad))
    kt = seen_keys.reshape(-1, tile)
    st = seen_scores.reshape(-1, tile)

    def body(carry, xs):
        acc_s, acc_f, base = carry
        k, s = xs
        ds, df = kref.rank_join_lookup_ref(k, s, probe_keys, seen_cnt, base)
        return (acc_s + ds, acc_f | df, base + tile), None

    (scores, found, _), _ = jax.lax.scan(
        body,
        (jnp.zeros_like(probe_keys, jnp.float32),
         jnp.zeros(probe_keys.shape, bool), jnp.int32(0)),
        (kt, st))
    return jnp.where(found, scores, 0.0), found


class MergedStreams(NamedTuple):
    """Gathered source lists for every stream of one query.

    A stream = a triple pattern + its relaxations. Raw (non-relaxed) streams
    simply have every relaxation source masked off. Scores are pre-scaled by
    the relaxation weights, so merge order is the paper's weighted order.
    """

    keys: jax.Array        # (T, R1, L) int32
    scores: jax.Array      # (T, R1, L) f32 (already weight-scaled)
    lengths: jax.Array     # (T, R1) int32 (0 for masked-off sources)
    stream_active: jax.Array  # (T,) bool — padded query slots are False


def gather_streams(store, relax, pattern_ids: jax.Array,
                   relax_mask: jax.Array) -> MergedStreams:
    """Materialize stream views for a query given the plan's relax mask.

    ``relax_mask`` is the planner's (T, R) per-relaxation mask: source r+1
    of stream t is live iff relaxation slot r of pattern t is real (not
    padding) *and* the plan enabled it.
    """
    T = pattern_ids.shape[0]
    R = relax.ids.shape[1]
    safe_pid = jnp.where(pattern_ids == PAD_KEY, 0, pattern_ids)

    # Source 0 = the original pattern, weight 1.
    rel_ids = relax.ids[safe_pid]                      # (T, R)
    rel_w = relax.weights[safe_pid]                    # (T, R)
    src_ids = jnp.concatenate([safe_pid[:, None], jnp.where(
        rel_ids == PAD_KEY, 0, rel_ids)], axis=1)      # (T, R+1)
    src_valid = jnp.concatenate([
        (pattern_ids != PAD_KEY)[:, None],
        (rel_ids != PAD_KEY) & relax_mask,
    ], axis=1)                                         # (T, R+1)
    weights = jnp.concatenate(
        [jnp.ones((T, 1), jnp.float32), rel_w], axis=1)

    keys = store.keys[src_ids]                         # (T, R+1, L)
    scores = store.scores[src_ids] * weights[..., None]
    lengths = jnp.where(src_valid, store.lengths[src_ids], 0)
    keys = jnp.where(src_valid[..., None], keys, PAD_KEY)
    scores = jnp.where(src_valid[..., None], scores, 0.0)
    return MergedStreams(keys=keys, scores=scores, lengths=lengths,
                         stream_active=pattern_ids != PAD_KEY)


def pull_block(keys: jax.Array, scores: jax.Array, lengths: jax.Array,
               cursors: jax.Array, block: int):
    """Pull the next ``block`` items of one merged stream.

    Args:
      keys/scores: (R1, L); lengths/cursors: (R1,).
    Returns (blk_keys (B,), blk_scores (B,) sorted desc, new_cursors (R1,)).
    """
    R1, L = keys.shape
    # Pad one block so dynamic_slice near the tail never clamps its start
    # (clamping would silently re-read earlier items and corrupt the merge).
    keys_p = jnp.concatenate(
        [keys, jnp.full((R1, block), PAD_KEY, keys.dtype)], axis=1)
    scores_p = jnp.concatenate(
        [scores, jnp.full((R1, block), NEG_INF, scores.dtype)], axis=1)

    def window(r):
        k = jax.lax.dynamic_slice_in_dim(keys_p[r], cursors[r], block)
        s = jax.lax.dynamic_slice_in_dim(scores_p[r], cursors[r], block)
        pos = cursors[r] + jnp.arange(block)
        ok = pos < lengths[r]
        return jnp.where(ok, k, PAD_KEY), jnp.where(ok, s, NEG_INF)

    wk, ws = jax.vmap(window)(jnp.arange(R1))          # (R1, B)
    flat_k, flat_s = wk.reshape(-1), ws.reshape(-1)
    top_s, top_i = jax.lax.top_k(flat_s, block)        # sorted desc
    blk_keys = flat_k[top_i]
    src_of = top_i // block
    taken = (top_s > NEG_INF)
    # Advance each source cursor by the number of its items taken.
    adv = jax.vmap(lambda r: jnp.sum((src_of == r) & taken))(jnp.arange(R1))
    new_cursors = jnp.minimum(cursors + adv, lengths)
    blk_keys = jnp.where(taken, blk_keys, PAD_KEY)
    blk_scores = jnp.where(taken, top_s, NEG_INF)
    return blk_keys, blk_scores, new_cursors


def dedup_block(blk_keys: jax.Array, blk_scores: jax.Array):
    """Mask duplicate keys inside a (desc-sorted) block, keeping the max.

    The block is sorted by score desc, so the first occurrence is the max —
    exactly the paper's S(A) = max over relaxed rewritings (Definition 8).
    """
    B = blk_keys.shape[0]
    eq = blk_keys[None, :] == blk_keys[:, None]
    lower = jnp.tril(jnp.ones((B, B), bool), k=-1)
    dup = jnp.any(eq & lower, axis=1) & (blk_keys != PAD_KEY)
    keys = jnp.where(dup, PAD_KEY, blk_keys)
    scores = jnp.where(dup, NEG_INF, blk_scores)
    return keys, scores


def merged_head_score(keys, scores, lengths, cursors):
    """Score of the next item the merged stream would emit (-inf if dry)."""
    R1, L = keys.shape
    idx = jnp.minimum(cursors, L - 1)
    head = jnp.take_along_axis(scores, idx[:, None], axis=1)[:, 0]
    alive = cursors < lengths
    return jnp.max(jnp.where(alive, head, NEG_INF))


def topk_insert(buf_keys, buf_scores, cand_keys, cand_scores, k: int):
    """Merge candidates into a running top-k buffer, dedup-safe.

    Candidates are unique within a block, but a key evicted from a capped
    seen ring can be re-pulled from a later (lower-scored) source and
    re-emitted — without dedup the same answer key would occupy two top-k
    slots. The buffer copy always wins: a re-pulled candidate carries the
    same join contribution (each stream's seen score for a key is fixed at
    its first pull) and a ≤ pull score, so dropping candidate keys already
    in the buffer keeps each key's max — without a stable argsort over the
    concatenation, which lowers to a batched sort the CPU backend runs an
    order of magnitude slower than this mask + ``top_k`` under the batch
    executor's lane vmap.
    """
    dup = ((cand_keys[:, None] == buf_keys[None, :]) &
           (cand_keys != PAD_KEY)[:, None])            # (B, k)
    drop = jnp.any(dup, axis=1)
    keys = jnp.concatenate([buf_keys, jnp.where(drop, PAD_KEY, cand_keys)])
    scores = jnp.concatenate([buf_scores,
                              jnp.where(drop, NEG_INF, cand_scores)])
    top_s, top_i = jax.lax.top_k(scores, k)
    return keys[top_i], top_s
