"""The benchmark's numpy reference against the program's oracle, and the
control: the same comparison fails on scores rounded to bfloat16."""
import sys
from pathlib import Path

# The benchmark is the package ``bench`` at the root of the checkout.
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import json

import numpy as np
import pytest
import jax.numpy as jnp

from bench import cell, gen, reference
from repro.core import engine, kg

N_ENT, L, R = 384, 48, 3
LIMIT = json.loads((cell.ROOT / "bench" / "configs" / "twitter-l8k.json")
                   .read_text())["checks"]["score_gap"]


@pytest.fixture(scope="module")
def world():
    raw = gen.generate("xkg", seed=2**31 + 11, n_entities=N_ENT, list_len=L,
                       n_queries=6, n_relax=R, tp_range=(2, 4))
    store = kg.build_store(raw.patterns, list_len=L)
    relax = kg.build_relax_table(len(raw.patterns), raw.rules, max_relax=R)
    ref = reference.Reference(raw.patterns, raw.rules, N_ENT, R)
    return raw, store, relax, ref


def _masks(T, seed):
    rng = np.random.default_rng(seed)
    return [None, np.ones((T, R), bool), np.zeros((T, R), bool),
            rng.random((T, R)) < 0.5]


@pytest.mark.parametrize("qi", range(6))
def test_reference_equals_naive_full_scan(world, qi):
    raw, store, relax, ref = world
    q = raw.queries[qi]
    T = int((q >= 0).sum())
    for mask in _masks(T, qi):
        full = None if mask is None else np.zeros((len(q), R), bool)
        if full is not None:
            full[:T] = mask
        keys, scores = engine.naive_full_scan(
            store, relax, jnp.asarray(q), 5, N_ENT,
            None if full is None else jnp.asarray(full))
        tot = ref.totals(q, mask)
        rk, rs = ref.topk(tot, 5)
        np.testing.assert_allclose(np.asarray(scores), rs, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(keys), rk)
        assert reference.score_gap(np.asarray(keys), np.asarray(scores),
                                   rs, tot) < LIMIT


def test_bfloat16_scores_fail_the_comparison(world):
    raw, store, relax, ref = world
    low = kg.build_store(raw.patterns, list_len=L)
    low = type(low)(**{**low.__dict__, "scores": low.scores.astype(
        jnp.bfloat16).astype(jnp.float32)})
    gaps = []
    for q in raw.queries:
        keys, scores = engine.naive_full_scan(low, relax, jnp.asarray(q), 5,
                                              N_ENT)
        tot = ref.totals(q)
        _, rs = ref.topk(tot, 5)
        gaps.append(reference.score_gap(np.asarray(keys), np.asarray(scores),
                                        rs, tot))
    assert max(gaps) > 3 * LIMIT


def test_score_gap_reads_missing_and_wrong_answers():
    tot = np.full(10, -np.inf)
    tot[[2, 5, 7]] = [3.0, 2.0, 1.0]
    ref = reference.Reference([], {}, 10, 1)
    rk, rs = ref.topk(tot, 3)
    assert list(rk) == [2, 5, 7]
    assert reference.score_gap(rk, rs, rs, tot) == 0.0
    # a dropped answer, a non-answer, and a right key with a wrong score
    assert reference.score_gap(np.array([2, 5, -1]),
                               np.array([3.0, 2.0, -np.inf]), rs, tot) \
        == pytest.approx(1 / 3)
    assert reference.score_gap(np.array([2, 5, 1]),
                               np.array([3.0, 2.0, 1.0]), rs, tot) \
        == pytest.approx(1 / 3)
    assert reference.score_gap(np.array([2, 5, 7]),
                               np.array([3.0, 2.0, 1.3]), rs, tot) \
        == pytest.approx(0.1)
    assert reference.precision(np.array([2, 5, 1]), rk) == pytest.approx(2 / 3)
