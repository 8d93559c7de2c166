"""Distributed engine == single-device engine (8 placeholder devices).

Runs in a subprocess because the device count must be fixed before jax
initializes (the main test process keeps 1 device).
"""
import subprocess
import sys
import os

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.data import kg_synth
from repro.core import engine, distributed
from repro.core.types import EngineConfig

wl = kg_synth.tiny_workload(seed=3, n_queries=3, n_entities=384, list_len=48)
P = wl.store.keys.shape[0]
lists = []
for p in range(P):
    n = int(wl.store.lengths[p])
    lists.append((np.asarray(wl.store.keys[p][:n]),
                  np.asarray(wl.store.scores[p][:n])))
mesh = make_mesh((4, 2), ("data", "model"))
skg = distributed.build_sharded_kg(lists, wl.relax, 8, mesh=mesh)
assert len({s.device for s in skg.stores.keys.addressable_shards}) == 8
# Every shard plans from key bitmaps at the unsharded store's width.
assert skg.stores.key_bits.shape[-1] == wl.store.key_bits.shape[-1] > 0
cfg = EngineConfig(block=8, k=5, grid_bins=128)
for i in range(len(wl.queries)):
    q = jnp.asarray(wl.queries[i])
    rd = distributed.run_query_sharded(skg, q, cfg, "trinit", mesh)
    r1 = engine.run_query(wl.store, wl.relax, q, cfg, "trinit")
    assert np.allclose(np.asarray(rd.scores), np.asarray(r1.scores),
                       rtol=1e-5), (i, rd.scores, r1.scores)
    sd = distributed.run_query_sharded(skg, q, cfg, "specqp", mesh)
    s1 = engine.run_query(wl.store, wl.relax, q, cfg, "specqp")
    assert np.array_equal(np.asarray(sd.relax_mask),
                          np.asarray(s1.relax_mask)), i

# batched sharded entrypoint
fn = distributed.make_batched_sharded_fn(cfg, "specqp", mesh)
qs = jnp.asarray(wl.queries[:2])
batch = fn(skg.stores, skg.relax, skg.global_stats, qs)
for i in range(2):
    s1 = engine.run_query(wl.store, wl.relax, qs[i], cfg, "specqp")
    assert np.allclose(np.asarray(batch.scores[i]), np.asarray(s1.scores),
                       rtol=1e-5), i

# sketched cardinalities: local estimates psum into one global plan; the
# run must produce a well-formed unique top-k (estimates are approximate,
# so no bit-exact mask equality with the single-device plan is asserted).
cfg_sk = EngineConfig(block=8, k=5, grid_bins=128, cardinality_mode="sketch")
q = jnp.asarray(wl.queries[0])
rsk = distributed.run_query_sharded(skg, q, cfg_sk, "specqp", mesh)
got = [int(x) for x in np.asarray(rsk.keys) if x >= 0]
assert len(got) == len(set(got)), got
assert np.isfinite(np.asarray(rsk.scores)).any()
print("DISTRIBUTED_OK")
"""


def test_shard_workload_survives_hash_skew():
    """Regression: list_len used to be a 2·mean+16 heuristic, which under
    hash imbalance (every key landing on one shard) undersized the shard
    stores and tripped build_store's length assert. The true per-shard
    max must be used."""
    import numpy as np
    from repro.core import distributed

    n_shards = 4
    cand = np.arange(50_000)
    hot = cand[distributed.mix_hash(cand, n_shards) == 0][:256]
    assert len(hot) == 256
    lists = [(hot.astype(np.int32), np.linspace(2.0, 1.0, 256))]
    stores, g_stats = distributed.shard_workload(lists, n_shards)
    lengths = np.asarray(stores.lengths)            # (S, P)
    assert lengths.shape == (n_shards, 1)
    assert int(lengths.sum()) == 256                # nothing dropped
    assert int(lengths[0, 0]) == 256                # all on the hot shard
    # Every key survived the round-trip onto shard 0.
    keys0 = np.asarray(stores.keys)[0, 0]
    assert set(keys0[keys0 >= 0].tolist()) == set(hot.tolist())


def test_sharded_key_bits_plan_like_one_store():
    """Shard stores built with the global ``key_words`` split the single
    store's key bitmaps, their local exact counts sum to its counts, and
    the plan from the summed counts is the single-device plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import distributed, engine, estimator, plangen
    from repro.core.types import EngineConfig, PAD_KEY
    from repro.data import kg_synth

    wl = kg_synth.tiny_workload(seed=3, n_queries=4, n_entities=384,
                                list_len=48)
    P = wl.store.keys.shape[0]
    lists = [(np.asarray(wl.store.keys[p][:int(wl.store.lengths[p])]),
              np.asarray(wl.store.scores[p][:int(wl.store.lengths[p])]))
             for p in range(P)]
    stores, g_stats = distributed.shard_workload(lists, 4)
    bits = np.asarray(stores.key_bits)                       # (S, P, Wk)
    assert bits.shape[1:] == wl.store.key_bits.shape and bits.shape[2] > 0
    np.testing.assert_array_equal(np.bitwise_or.reduce(bits, axis=0),
                                  np.asarray(wl.store.key_bits))
    assert not np.any(bits[0] & bits[1])                     # disjoint keys
    cfg = EngineConfig(block=8, k=5, grid_bins=128)

    @jax.jit
    def counts(store, q):
        active = q != PAD_KEY
        n, n_rel = estimator.exact_cardinalities(store, wl.relax, q, active)
        return n, n_rel, estimator.joinable_counts(store, wl.relax, q,
                                                   active)

    for q in map(jnp.asarray, wl.queries):
        local = [counts(jax.tree_util.tree_map(lambda x: x[s], stores), q)
                 for s in range(4)]
        n, n_rel, n_join = (sum(c[i] for c in local) for i in range(3))
        for a, b in zip((n, n_rel, n_join), counts(wl.store, q)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        active = q != PAD_KEY
        e_qk, e_q1 = estimator.score_estimates_from_cards(
            jnp.asarray(g_stats), wl.relax, q, active, n, n_rel, cfg.k,
            cfg.grid_bins)
        rel_exists = wl.relax.ids[jnp.where(active, q, 0)] != PAD_KEY
        mask = plangen.plan_from_estimates(e_qk, e_q1, n_join, rel_exists,
                                           active, cfg.plan_slack)
        np.testing.assert_array_equal(
            np.asarray(mask), np.asarray(engine.plan_for_mode(
                wl.store, wl.relax, q, cfg, "specqp")))


@pytest.mark.slow
def test_distributed_engine_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "DISTRIBUTED_OK" in out.stdout, out.stdout + out.stderr
