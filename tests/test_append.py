"""The seen-ring append (``engine._append_block``) against the one-hot
mask-and-reduce it replaced, kept here as the oracle, and a guard that
the executor's compiled program builds no (N, B) array again."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import small_workload, TEST_GRID_BINS
from repro.core import engine, scopes
from repro.core.types import EngineConfig, PAD_KEY, NEG_INF

B = 8
R1, L = 4, 12
# Uncapped ring: R1·L + 2B = 64 items, 8 blocks. Capped: 20 → 24, 3 blocks.
N_OPEN = engine._seen_size(R1, L, EngineConfig(block=B))
N_CAP = engine._seen_size(R1, L, EngineConfig(block=B, seen_cap=20))
# t_star's items appended so far, as (ring length, seen_cnt): empty, mid
# ring, the last block, and wrapped past 2N under a seen_cap.
POSITIONS = {
    "empty": (N_OPEN, 0),
    "mid": (N_OPEN, N_OPEN // 2),
    "last_block": (N_OPEN, N_OPEN - B),
    "wrapped_cap": (N_CAP, 2 * N_CAP + B),
}
TS = [(t, s) for t in (1, 2, 3, 4) for s in range(t)]


def _one_hot_append(seen_keys, seen_scores, seen_cnt, t_star, blk_k, blk_s):
    """The append as ``_step`` wrote it before: for every stream, an
    (N, B) one-hot of the block's slots, keys and scores summed through
    it, the result kept in row ``t_star`` only."""
    T, N = seen_keys.shape
    B_ = blk_k.shape[0]
    blk_s_store = jnp.where(blk_s == NEG_INF, 0.0, blk_s)

    def append(t):
        start = seen_cnt[t] % jnp.int32(N)
        rel = jnp.arange(N) - start
        oh = rel[:, None] == jnp.arange(B_)[None, :]
        in_win = (rel >= 0) & (rel < B_)
        upd_k = jnp.where(
            in_win, jnp.sum(jnp.where(oh, blk_k[None, :], 0), axis=1),
            seen_keys[t])
        upd_s = jnp.where(
            in_win,
            jnp.sum(jnp.where(oh, blk_s_store[None, :], 0.0), axis=1),
            seen_scores[t])
        sel = t == t_star
        return (jnp.where(sel, upd_k, seen_keys[t]),
                jnp.where(sel, upd_s, seen_scores[t]))
    keys, scores = jax.vmap(append)(jnp.arange(T))
    cnt = seen_cnt + jnp.where(jnp.arange(T) == t_star, B_, 0).astype(
        jnp.int32)
    return keys, scores, cnt


_NEW = jax.jit(engine._append_block)
_OLD = jax.jit(_one_hot_append)
_NEW_LANES = jax.jit(jax.vmap(engine._append_block))
_OLD_LANES = jax.jit(jax.vmap(_one_hot_append))


def _inputs(rng, T, N, t_star, cnt):
    """A ring of random live data (PAD slots included), block-aligned
    counts for the other streams, and a block with a PAD tail whose
    scores are NEG_INF, plus a dropped (already seen) slot."""
    keys = rng.integers(0, 1000, (T, N)).astype(np.int32)
    keys[rng.random((T, N)) < 0.1] = PAD_KEY
    scores = rng.random((T, N)).astype(np.float32)
    counts = rng.integers(0, 4 * N // B, T).astype(np.int32) * B
    counts[t_star] = cnt
    blk_k = rng.integers(1000, 2000, B).astype(np.int32)
    blk_s = np.sort(rng.random(B).astype(np.float32))[::-1].copy()
    tail = int(rng.integers(1, B))
    blk_k[tail:] = PAD_KEY
    blk_s[tail:] = -np.inf
    blk_k[0], blk_s[0] = PAD_KEY, -np.inf
    return (jnp.asarray(keys), jnp.asarray(scores), jnp.asarray(counts),
            jnp.int32(t_star), jnp.asarray(blk_k), jnp.asarray(blk_s))


def _assert_same(new, old):
    """Equal keys and counts, and scores equal bit for bit."""
    (nk, ns, nc), (ok, os_, oc) = ([np.asarray(x) for x in r]
                                   for r in (new, old))
    np.testing.assert_array_equal(nk, ok)
    np.testing.assert_array_equal(ns.view(np.uint32), os_.view(np.uint32))
    np.testing.assert_array_equal(nc, oc)


@pytest.mark.parametrize("pos", sorted(POSITIONS))
@pytest.mark.parametrize("T,t_star", TS)
def test_block_select_append_equals_one_hot(T, t_star, pos):
    """Bit for bit equal to the one-hot append, for every stream count and
    t_star, at every position of the ring, wrapped included; only t_star's
    block changes."""
    N, cnt = POSITIONS[pos]
    rng = np.random.default_rng([T, t_star, sorted(POSITIONS).index(pos)])
    args = _inputs(rng, T, N, t_star, cnt)
    new = _NEW(*args)
    _assert_same(new, _OLD(*args))
    start = cnt % N
    changed = np.nonzero(np.asarray(new[0]) != np.asarray(args[0]))
    assert set(changed[0]) <= {t_star}
    assert all(start <= j < start + B for j in changed[1])


@pytest.mark.parametrize("T", (1, 2, 3, 4))
def test_block_select_append_equals_one_hot_under_lane_vmap(T):
    """Two lanes, each with its own t_star and counts (one of them
    wrapped), through a vmap as the executor runs the step."""
    N = N_OPEN
    rng = np.random.default_rng(T)
    lanes = [_inputs(rng, T, N, T - 1, N - B),
             _inputs(rng, T, N, 0, 3 * N + 2 * B)]
    args = [jnp.stack(leaves) for leaves in zip(*lanes)]
    _assert_same(_NEW_LANES(*args), _OLD_LANES(*args))


def test_executor_program_builds_no_ring_by_block_array():
    """The compiled single-query program holds the (T, N) rings but no
    array with the ring length next to the block, as the one-hot's (N, B)
    was, and still names the append's scope."""
    wl = small_workload(seed=0, n_queries=4)
    cfg = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
    N = engine._seen_size(wl.relax.ids.shape[1] + 1, wl.store.keys.shape[1],
                          cfg)
    text = engine.run_query.lower(wl.store, wl.relax,
                                  jnp.asarray(wl.queries[0]), cfg,
                                  "specqp").compile().as_text()
    shapes = re.findall(r"\[([0-9,]+)\]", text)
    assert any(s.endswith(f",{N}") for s in shapes), "ring not found"
    assert not [s for s in shapes if re.search(rf"\b{N},{cfg.block}\b", s)]
    assert scopes.APPEND in text
