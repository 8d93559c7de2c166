"""Jit'd dispatch wrappers for the Pallas kernels.

Every op picks the Pallas kernel on TPU (interpret=False) and either the
interpret-mode kernel or the pure-jnp oracle elsewhere. Callers can force a
path with ``impl`` ∈ {"auto", "pallas", "ref"} — benchmarks and tests use
that to compare paths on identical inputs. Whether a kernel is compiled or
interpreted is never the caller's choice: it is compiled exactly when the
default backend is a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import rank_join as _rank_join
from repro.kernels import merge_topk as _merge_topk
from repro.kernels import topk_score as _topk_score
from repro.kernels import embedding_bag as _embedding_bag
from repro.kernels import neigh_agg as _neigh_agg
from repro.kernels import flash_attention as _flash_attention


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> tuple[bool, bool]:
    """→ (use_pallas, interpret)."""
    if impl == "ref":
        return False, True
    if impl == "pallas":
        return True, not _on_tpu()
    return (True, False) if _on_tpu() else (False, True)


def rank_join_lookup(seen_keys, seen_scores, probe_keys, seen_cnt,
                     impl: str = "auto"):
    use_pallas, interp = _resolve(impl)
    if use_pallas:
        return _rank_join.rank_join_lookup(
            seen_keys, seen_scores, probe_keys, seen_cnt, interpret=interp)
    return _ref.rank_join_lookup_ref(
        seen_keys, seen_scores, probe_keys, seen_cnt)


def merge_topk(window_keys, window_scores, block: int, impl: str = "auto"):
    use_pallas, interp = _resolve(impl)
    if use_pallas:
        return _merge_topk.merge_topk(
            window_keys, window_scores, block, interpret=interp)
    return _ref.merge_topk_ref(window_keys, window_scores, block)


def topk_score_pruned(query, cands, block_bounds, k: int, tile: int = 512,
                      impl: str = "auto"):
    use_pallas, interp = _resolve(impl)
    if use_pallas:
        return _topk_score.topk_score_pruned(
            query, cands, block_bounds, k, tile, interpret=interp)
    return _ref.topk_score_pruned_ref(query, cands, block_bounds, k, tile)


block_bounds_cauchy = _topk_score.block_bounds_cauchy


def embedding_bag(table, ids, weights, impl: str = "auto"):
    use_pallas, interp = _resolve(impl)
    if use_pallas and not interp:
        # The scalar-prefetch gather only pays off on real TPU DMA; the
        # interpret-mode emulation is O(B*S) python — use the oracle on CPU.
        return _embedding_bag.embedding_bag(table, ids, weights,
                                            interpret=False)
    if impl == "pallas":
        return _embedding_bag.embedding_bag(table, ids, weights,
                                            interpret=interp)
    return _ref.embedding_bag_ref(table, ids, weights)


def neigh_softmax_agg(logits, feats, mask, tile_n: int = 128,
                      impl: str = "auto"):
    use_pallas, interp = _resolve(impl)
    if use_pallas and not interp:
        return _neigh_agg.neigh_softmax_agg(logits, feats, mask,
                                            tile_n=tile_n, interpret=False)
    if impl == "pallas":
        return _neigh_agg.neigh_softmax_agg(logits, feats, mask,
                                            tile_n=tile_n, interpret=interp)
    return _ref.neigh_softmax_agg_ref(logits, feats, mask)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, impl: str = "auto",
                    tile_q: int = 128, tile_k: int = 128):
    use_pallas, interp = _resolve(impl)
    if use_pallas and not interp:
        return _flash_attention.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, tile_q=tile_q, tile_k=tile_k, interpret=False)
    if impl == "pallas":
        return _flash_attention.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, tile_q=tile_q, tile_k=tile_k, interpret=interp)
    return _ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale)
