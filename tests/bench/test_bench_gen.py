"""The benchmark's generator copy, its arrival schedules, and its refusal
to run off a TPU."""
import sys
from pathlib import Path

# The benchmark is the package ``bench`` at the root of the checkout.
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import cell, gen, traffic
from repro.core import kg
from repro.data import kg_synth


@pytest.mark.parametrize("kind,seed", [("xkg", 3), ("twitter", 2**31 + 7)])
def test_generator_copy_matches_kg_synth(kind, seed):
    want = kg_synth.make_workload(kind, seed=seed, n_entities=600,
                                  list_len=40, n_queries=5)
    raw = gen.generate(kind, seed=seed, n_entities=600, list_len=40,
                       n_queries=5)
    np.testing.assert_array_equal(raw.queries, want.queries)
    store = kg.build_store(raw.patterns, list_len=40)
    relax = kg.build_relax_table(len(raw.patterns), raw.rules,
                                 max_relax=raw.n_relax)
    for got, exp in ((store, want.store), (relax, want.relax)):
        for name, a in got.__dict__.items():
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(getattr(exp, name)),
                                          err_msg=name)


@pytest.mark.parametrize("arrival", ["paced", "poisson"])
def test_schedule_is_fixed_by_the_seed(arrival):
    mix = {"arrival": arrival, "rate_qps": 3.0}
    a = traffic.schedule(mix, 20.0, np.random.default_rng([5, 1]))
    b = traffic.schedule(mix, 20.0, np.random.default_rng([5, 1]))
    c = traffic.schedule(mix, 20.0, np.random.default_rng([6, 1]))
    np.testing.assert_array_equal(a.offsets, b.offsets)
    np.testing.assert_array_equal(a.query, b.query)
    assert not np.array_equal(a.query, c.query)
    assert np.all(np.diff(a.offsets) >= 0)
    assert a.offsets.min() >= 0 and a.offsets.max() < 20.0
    assert len(a.offsets) == len(c.offsets) == a.pool == 60
    assert sorted(a.query) == list(range(a.pool))
    if arrival == "paced":
        np.testing.assert_allclose(a.offsets, np.arange(60) / 3.0)
        np.testing.assert_array_equal(a.offsets, c.offsets)
    else:
        assert not np.array_equal(a.offsets, c.offsets)


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule({"arrival": "onoff", "rate_qps": 1.0}, 5.0,
                         np.random.default_rng(0))


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "twitter.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_off_a_tpu():
    p = _run(cell.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(cell.ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(cell.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".jax_cache",
                                                      "__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_sizes_seed_fixes_the_sizes_and_the_seed_the_data():
    a, b = (gen.generate("xkg", seed=s, n_entities=600, list_len=40,
                         n_queries=6, sizes_seed=[9, 0]) for s in (1, 2))
    np.testing.assert_array_equal(a.queries, b.queries)
    assert [[w for _, w in rl] for rl in a.rules.values()] == \
        [[w for _, w in rl] for rl in b.rules.values()]
    la = [len(k) for k, _ in a.patterns]
    lb = [len(k) for k, _ in b.patterns]
    assert np.abs(np.subtract(la, lb)).max() <= 0.25 * 40
    assert any(not np.array_equal(ka, kb) for (ka, _), (kb, _)
               in zip(a.patterns, b.patterns))


@pytest.mark.parametrize("kind", ["xkg", "twitter"])
def test_filler_fills_every_slot_and_leaves_the_pool_as_drawn(kind):
    kw = dict(seed=11, n_entities=600, list_len=40, n_queries=3)
    pool = gen.generate(kind, **kw)
    full = gen.generate(kind, **kw, n_patterns=200)
    assert len(full.patterns) == 200 and len(pool.patterns) < 200
    np.testing.assert_array_equal(full.queries, pool.queries)
    for (ka, sa), (kb, sb) in zip(pool.patterns, full.patterns):
        np.testing.assert_array_equal(ka, kb)
        np.testing.assert_array_equal(sa, sb)
    assert all(len(k) > 0 for k, _ in full.patterns)
    for p, rl in pool.rules.items():
        assert full.rules[p] == rl
    assert all(p < 200 and r < 200 for p, rl in full.rules.items()
               for r, _ in rl)
    relax = kg.build_relax_table(200, full.rules, max_relax=full.n_relax)
    assert relax.ids.shape[0] == 200
    with pytest.raises(ValueError):
        gen.generate(kind, **kw, n_patterns=len(pool.patterns) - 1)
