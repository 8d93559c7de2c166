"""Pallas kernel sweeps: shapes/dtypes vs the ref.py oracles (interpret)."""
import numpy as np
import jax
import jax.extend
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (ref, rank_join, merge_topk, topk_score,
                           embedding_bag, neigh_agg, flash_attention)
from repro.kernels.sortnet import bitonic_topk_desc

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("N,B,frac", [(256, 16, 0.5), (1000, 64, 0.7),
                                      (513, 32, 1.0), (4096, 128, 0.3)])
def test_rank_join_lookup(N, B, frac):
    keys = RNG.choice(100000, N, replace=False).astype(np.int32)
    cnt = np.int32(int(N * frac))
    keys[cnt:] = -1
    scores = RNG.random(N).astype(np.float32)
    probes = np.concatenate([
        RNG.choice(keys[:max(cnt, 1)], B // 2),
        RNG.choice(200000, B - B // 2)]).astype(np.int32)
    a = rank_join.rank_join_lookup(jnp.asarray(keys), jnp.asarray(scores),
                                   jnp.asarray(probes), jnp.int32(cnt))
    b = ref.rank_join_lookup_ref(jnp.asarray(keys), jnp.asarray(scores),
                                 jnp.asarray(probes), jnp.int32(cnt))
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def _dot_precisions(jaxpr) -> list:
    """The ``precision`` of every dot_general in ``jaxpr``, in program
    order, descending into sub-jaxprs (jit bodies, Pallas kernel bodies)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if isinstance(sub, jax.extend.core.Jaxpr):
                out.extend(_dot_precisions(sub))
    return out


def test_rank_join_oracle_contracts_at_kernel_precision():
    """The oracle must round the score contraction as the kernel does: at
    default precision the TPU matmul passes f32 operands as bf16, so an
    oracle and kernel that disagree on precision disagree in the 3rd
    digit on the chip (and agree on the CPU, where nothing would show)."""
    args = (jnp.zeros((512,), jnp.int32), jnp.zeros((512,), jnp.float32),
            jnp.zeros((16,), jnp.int32), jnp.int32(0))
    kern = _dot_precisions(jax.make_jaxpr(
        lambda *a: rank_join.rank_join_lookup(*a, interpret=True))(*args)
        .jaxpr)
    orac = _dot_precisions(jax.make_jaxpr(ref.rank_join_lookup_ref)(*args)
                           .jaxpr)
    assert len(kern) == 2 and kern == orac, (kern, orac)
    assert kern[0] is not None                 # the score contraction


def test_rank_join_matches_step_probe_semantics():
    """Pre-built equivalence oracle for the Pallas swap-in: interpret-mode
    ``rank_join_lookup`` vs the exact jnp probe the unified executor's
    ``_step`` runs today (``ops.lookup_scores`` with use_pallas=False), on
    the awkward inputs the engine actually produces — an N that is NOT a
    tile multiple (remainder tile is all padding), duplicate keys inside
    the live window (both probes must SUM every live match identically),
    a duplicate whose second copy sits past seen_cnt (dead — must not
    contribute), and PAD probes/slots."""
    from repro.core import operators as ops

    rng = np.random.default_rng(11)
    N, tile = 700, 256                     # 700 % 256 != 0
    cnt = np.int32(520)                    # live window < N
    keys = rng.choice(50000, N, replace=False).astype(np.int32)
    scores = rng.random(N).astype(np.float32)
    # Duplicates inside the live window: key at slot 3 reappears at slots
    # 300 and 517 (scores differ — the summed score exposes any probe
    # that stops at the first hit).
    keys[300] = keys[517] = keys[3]
    # Duplicate straddling the live boundary: second copy is dead.
    keys[600] = keys[40]
    keys[cnt:] = np.where(np.arange(N - cnt) % 3 == 0, -1, keys[cnt:])
    probes = np.concatenate([
        [keys[3], keys[40], -1],           # dup hit, straddler, PAD probe
        rng.choice(keys[:cnt], 16),        # live hits (some dups again)
        rng.choice(np.arange(60000, 61000), 13),   # guaranteed misses
    ]).astype(np.int32)

    args = (jnp.asarray(keys), jnp.asarray(scores), jnp.asarray(probes),
            jnp.int32(cnt))
    ks, kf = rank_join.rank_join_lookup(*args, tile_n=tile, interpret=True)
    es, ef = ops.lookup_scores(*args, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(kf), np.asarray(ef))
    np.testing.assert_allclose(np.asarray(ks), np.asarray(es), rtol=1e-6)
    # The construction really exercised what it claims.
    assert np.asarray(ef)[0] and np.asarray(ef)[1] and not np.asarray(ef)[2]
    want_dup = float(scores[3] + scores[300] + scores[517])
    np.testing.assert_allclose(float(np.asarray(ks)[0]), want_dup, rtol=1e-6)
    np.testing.assert_allclose(float(np.asarray(ks)[1]), float(scores[40]),
                               rtol=1e-6)


@pytest.mark.parametrize("R,W,B", [(4, 16, 16), (11, 64, 64), (3, 20, 32),
                                   (1, 128, 64)])
def test_merge_topk(R, W, B):
    wk = RNG.integers(0, 10000, (R, W)).astype(np.int32)
    ws = RNG.random((R, W)).astype(np.float32)
    ws[0, -2:] = -np.inf
    k1, s1 = merge_topk.merge_topk(jnp.asarray(wk), jnp.asarray(ws), B)
    k2, s2 = ref.merge_topk_ref(jnp.asarray(wk), jnp.asarray(ws), B)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@pytest.mark.parametrize("N,D,k,tile", [(2048, 64, 16, 512),
                                        (1024, 128, 8, 256)])
def test_topk_score_pruned(N, D, k, tile):
    q = RNG.standard_normal(D).astype(np.float32)
    c = RNG.standard_normal((N, D)).astype(np.float32)
    bounds = topk_score.block_bounds_cauchy(jnp.asarray(q), jnp.asarray(c),
                                            tile)
    s1, i1, n1 = topk_score.topk_score_pruned(
        jnp.asarray(q), jnp.asarray(c), bounds, k, tile)
    s2, i2, n2 = ref.topk_score_pruned_ref(
        jnp.asarray(q), jnp.asarray(c), bounds, k, tile)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # and (with sound bounds) equals the exact top-k
    s3, _ = ref.topk_score_ref(jnp.asarray(q), jnp.asarray(c), k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s3), rtol=1e-5)


def test_topk_score_prunes_sorted_blocks():
    """With block-norm-sorted candidates the kernel must skip tiles."""
    D, tile, k = 32, 256, 8
    mags = np.repeat([4.0, 2.0, 1.0, 0.5], tile)
    c = (RNG.standard_normal((4 * tile, D)) * mags[:, None] /
         np.sqrt(D)).astype(np.float32)
    q = RNG.standard_normal(D).astype(np.float32)
    bounds = topk_score.block_bounds_cauchy(jnp.asarray(q), jnp.asarray(c),
                                            tile)
    s1, i1, n1 = topk_score.topk_score_pruned(
        jnp.asarray(q), jnp.asarray(c), bounds, k, tile)
    s3, _ = ref.topk_score_ref(jnp.asarray(q), jnp.asarray(c), k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s3), rtol=1e-5)
    assert int(n1) < 4, "no tile was pruned"


@pytest.mark.parametrize("V,D,B,S", [(100, 32, 8, 4), (500, 64, 16, 8)])
def test_embedding_bag(V, D, B, S):
    table = RNG.standard_normal((V, D)).astype(np.float32)
    ids = RNG.integers(-1, V, (B, S)).astype(np.int32)
    w = RNG.random((B, S)).astype(np.float32)
    a = embedding_bag.embedding_bag(jnp.asarray(table), jnp.asarray(ids),
                                    jnp.asarray(w))
    b = ref.embedding_bag_ref(jnp.asarray(table), jnp.asarray(ids),
                              jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("N,MAXD,D", [(64, 16, 32), (130, 8, 64)])
def test_neigh_softmax_agg(N, MAXD, D):
    lg = RNG.standard_normal((N, MAXD)).astype(np.float32)
    ft = RNG.standard_normal((N, MAXD, D)).astype(np.float32)
    mk = RNG.random((N, MAXD)) > 0.3
    mk[0] = False
    a = neigh_agg.neigh_softmax_agg(jnp.asarray(lg), jnp.asarray(ft),
                                    jnp.asarray(mk), tile_n=64)
    b = ref.neigh_softmax_agg_ref(jnp.asarray(lg), jnp.asarray(ft),
                                  jnp.asarray(mk))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Sk,D,causal,win,cap,dtype",
    [(1, 4, 2, 128, 128, 64, True, None, None, np.float32),
     (2, 2, 2, 128, 256, 32, True, 64, None, np.float32),
     (1, 4, 1, 64, 64, 64, True, None, 30.0, np.float32),
     (1, 2, 2, 128, 128, 32, False, None, None, np.float32),
     (1, 2, 1, 128, 128, 32, True, None, None, np.dtype("bfloat16"))])
def test_flash_attention_kernel(B, Hq, Hkv, Sq, Sk, D, causal, win, cap,
                                dtype):
    q = (RNG.standard_normal((B, Hq, Sq, D)) * 0.3).astype(dtype)
    k = (RNG.standard_normal((B, Hkv, Sk, D)) * 0.3).astype(dtype)
    v = RNG.standard_normal((B, Hkv, Sk, D)).astype(dtype)
    a = flash_attention.flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal,
        window=win, softcap=cap, tile_q=64, tile_k=64)
    b = ref.flash_attention_ref(
        jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
        jnp.asarray(v, jnp.float32), causal=causal, window=win, softcap=cap)
    tol = 2e-2 if dtype == np.dtype("bfloat16") else 2e-4
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                               rtol=tol, atol=tol)


@given(st.integers(3, 8), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_bitonic_sort_property(log_l, seed):
    rng = np.random.default_rng(seed)
    L = 1 << log_l
    s = rng.standard_normal(L).astype(np.float32)
    p = rng.integers(0, 10**6, L).astype(np.int32)
    ss, pp = bitonic_topk_desc(jnp.asarray(s)[None], jnp.asarray(p)[None])
    np.testing.assert_allclose(np.asarray(ss[0]), -np.sort(-s), rtol=0)
    # payload permutation consistency
    order = np.argsort(-s, kind="stable")
    got = dict(zip(np.asarray(ss[0]).tolist(), np.asarray(pp[0]).tolist()))
    for sc, pay in zip(s[order], p[order]):
        if list(s).count(sc) == 1:
            assert got[sc] == pay
