"""A whole run of a cell at a tiny size on the CPU, with the look for a chip
skipped: sound, it is correct; with the control (scores served in
bfloat16) or with the timed path broken underneath, ``correct`` is false."""
import sys
from pathlib import Path

# The benchmark is the package ``bench`` at the root of the checkout.
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import dataclasses
import json
import time

import numpy as np
import pytest

from bench import cell
from repro.core import engine


def tiny_cell(kind="twitter"):
    c = cell.load_cell("twitter.steady")
    c.config = json.loads(json.dumps(c.config))
    c.config["generator"].update(kind=kind, list_len=48, n_entities=384,
                                 n_relax=3, tp_range=[2, 3])
    c.config["store_patterns"] = 256
    c.config["engine"] = {"block": 16, "k": 5, "grid_bins": 96}
    c.traffic = dict(c.traffic, rate_qps=5.0)
    return c


def run(c, seed, **kw):
    return cell.run(c, seed, 2.0, False, time.perf_counter(),
                    require_tpu=False, compile_cache=False, **kw)


def _patch_stream(monkeypatch, edit):
    orig = engine.run_query_stream_with_masks

    def broken(*a, **kw):
        res = orig(*a, **kw)
        keys, scores = np.array(res.keys), np.array(res.scores)
        edit(keys, scores)
        return dataclasses.replace(res, keys=keys, scores=scores)
    monkeypatch.setattr(engine, "run_query_stream_with_masks", broken)


@pytest.mark.parametrize("kind", ["xkg", "twitter"])
def test_sound_run_is_correct(kind):
    out = run(tiny_cell(kind), 2**31 + 17)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 10
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    assert set(m) == {"setup_s", "latency_p50_ms", "latency_p95_ms",
                      "answered_qps", "precision_at_k"}
    assert 0 < m["precision_at_k"]["value"] <= 1
    assert out["checks"]["score_gap"]["value"] < 1e-6


def test_control_bfloat16_scores_is_not_correct():
    out = run(tiny_cell(), 2**31 + 17, score_dtype="bfloat16")
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > \
        3 * out["checks"]["score_gap"]["limit"]


def _altered(keys, scores):
    scores[0, 0] += 0.01


def _half_left_out(keys, scores):
    m = keys.shape[0]
    keys[m // 2:] = -1
    scores[m // 2:] = -np.inf


def _swapped_key(keys, scores):
    keys[0, 0] = keys[0, 0] + 1


@pytest.mark.parametrize("edit", [_altered, _half_left_out, _swapped_key])
def test_broken_answers_are_not_correct(monkeypatch, edit):
    _patch_stream(monkeypatch, edit)
    out = run(tiny_cell(), 2**31 + 17)
    assert not out["correct"]
    assert out["checks"]["score_gap"]["value"] > \
        out["checks"]["score_gap"]["limit"]


def test_an_answer_that_never_comes_is_not_correct(monkeypatch):
    orig = engine.run_query_stream_with_masks
    calls = []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) > 9:      # warm-up makes 4 calls, then fail some
            raise RuntimeError("device lost")
        return orig(*a, **kw)
    monkeypatch.setattr(engine, "run_query_stream_with_masks", flaky)
    out = run(tiny_cell(), 2**31 + 17)
    assert not out["correct"]
    assert out["failed"] > 0
