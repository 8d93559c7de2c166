"""Pure-jnp oracles for every Pallas kernel (the correctness contracts).

Each function mirrors its kernel's semantics exactly; kernel tests sweep
shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PAD_KEY = jnp.int32(-1)
NEG_INF = jnp.float32(-jnp.inf)


def rank_join_lookup_ref(seen_keys, seen_scores, probe_keys, seen_cnt,
                         base=0):
    """Probe keys against a unique-key scored buffer.

    seen_keys/seen_scores: (N,); probe_keys: (B,); seen_cnt: () int32.
    ``base`` is the buffer position of slot 0, for a caller that probes a
    longer buffer tile by tile: slot i is live iff base + i < seen_cnt.
    Returns (scores (B,) f32 — 0 where missing, found (B,) bool).
    """
    n = seen_keys.shape[0]
    live = (base + jnp.arange(n)) < seen_cnt
    valid = (seen_keys != PAD_KEY) & live
    eq = (probe_keys[:, None] == seen_keys[None, :]) & valid[None, :]
    eqf = eq.astype(jnp.float32)
    # HIGHEST, as in the kernel: the TPU's default f32 matmul rounds
    # operands to bf16, which would return the matched score to 3
    # significant digits.
    scores = jnp.matmul(eqf, jnp.where(valid, seen_scores, 0.0),
                        precision=jax.lax.Precision.HIGHEST)
    found = (eqf @ valid.astype(jnp.float32)) > 0.5
    found = found & (probe_keys != PAD_KEY)
    return jnp.where(found, scores, 0.0), found


def merge_topk_ref(window_keys, window_scores, block: int):
    """Top-`block` of R source windows by score desc (merged-stream pull).

    window_keys/window_scores: (R, W). Returns (keys (block,),
    scores (block,)) sorted desc; ties broken by flat index asc.
    """
    flat_k = window_keys.reshape(-1)
    flat_s = window_scores.reshape(-1)
    top_s, top_i = jax.lax.top_k(flat_s, block)
    return flat_k[top_i], top_s


def topk_score_ref(query, cands, k: int):
    """Dot-score one query against all candidates and return top-k.

    query: (D,), cands: (N, D). Returns (scores (k,), idx (k,) int32).
    """
    scores = cands @ query
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_s, top_i.astype(jnp.int32)


def topk_score_pruned_ref(query, cands, block_bounds, k: int, tile: int):
    """Spec-QP speculative retrieval oracle: sequential tiles, skip a tile
    when its precomputed score upper bound cannot beat the running k-th.

    Returns (scores (k,), idx (k,), n_tiles_scored ()).
    Matches the kernel's *sequential* semantics (the set of scored tiles
    depends on visit order).
    """
    N, D = cands.shape
    n_tiles = N // tile
    buf_s = jnp.full((k,), NEG_INF, jnp.float32)
    buf_i = jnp.full((k,), -1, jnp.int32)
    scored = jnp.int32(0)

    def body(carry, j):
        buf_s, buf_i, scored = carry
        kth = buf_s[k - 1]
        run = block_bounds[j] > kth
        tile_sc = jax.lax.dynamic_slice_in_dim(cands, j * tile, tile) @ query
        tile_ix = j * tile + jnp.arange(tile, dtype=jnp.int32)
        tile_sc = jnp.where(run, tile_sc, NEG_INF)
        cat_s = jnp.concatenate([buf_s, tile_sc])
        cat_i = jnp.concatenate([buf_i, tile_ix])
        top_s, top_j = jax.lax.top_k(cat_s, k)
        return (top_s, cat_i[top_j], scored + run.astype(jnp.int32)), None

    (buf_s, buf_i, scored), _ = jax.lax.scan(
        body, (buf_s, buf_i, scored), jnp.arange(n_tiles))
    return buf_s, buf_i, scored


def embedding_bag_ref(table, ids, weights):
    """Weighted multi-hot embedding bag.

    table: (V, D); ids: (B, S) int32 (negative = inactive slot);
    weights: (B, S) f32. Returns (B, D) = Σ_s w[b,s] * table[ids[b,s]].
    """
    ok = ids >= 0
    safe = jnp.where(ok, ids, 0)
    gathered = table[safe]                       # (B, S, D)
    w = jnp.where(ok, weights, 0.0)
    return jnp.einsum("bsd,bs->bd", gathered, w)


def neigh_softmax_agg_ref(logits, feats, mask):
    """Fused edge-softmax + neighborhood aggregation (GAT hot loop).

    logits: (N, MAXD); feats: (N, MAXD, D); mask: (N, MAXD) bool.
    Returns (N, D) = Σ_d softmax_row(logits)_d * feats_d (masked rows with
    zero neighbors return zeros).
    """
    ml = jnp.where(mask, logits, NEG_INF)
    mx = jnp.max(ml, axis=1, keepdims=True)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.where(mask, jnp.exp(ml - mx), 0.0)
    den = jnp.sum(ex, axis=1, keepdims=True)
    w = ex / jnp.maximum(den, 1e-30)
    return jnp.einsum("nd,ndk->nk", w, feats)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        softcap: float | None = None,
                        scale: float | None = None):
    """Multi-head attention oracle with GQA, sliding window and softcap.

    q: (B, Hq, Sq, Dh); k/v: (B, Hkv, Sk, Dh). Hq % Hkv == 0.
    ``window``: each query attends to keys in (pos - window, pos].
    ``q_offset`` semantics: query i sits at absolute position
    Sk - Sq + i (decode-friendly).
    """
    B, Hq, Sq, Dh = q.shape
    Hkv = k.shape[1]
    Sk = k.shape[2]
    g = Hq // Hkv
    scale = scale if scale is not None else Dh ** -0.5
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = Sk - Sq + jnp.arange(Sq)
    kpos = jnp.arange(Sk)
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(m[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv)
