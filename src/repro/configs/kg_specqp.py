"""kg-specqp — the paper's own engine as a production serving config.

One device = one hash partition of the KG (DESIGN.md §2/§5); the serve
step answers a batch of star queries with the full Spec-QP pipeline
(statistics → PLANGEN → rank-join execution → two-level top-k merge).
This is the cell that §Perf hillclimbs as "most representative of the
paper's technique".
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs import base
from repro.core import distributed as dist
from repro.core import kg, sketches
from repro.core.types import TripleStore, RelaxTable, EngineConfig

ARCH = "kg-specqp"
FAMILY = "kg"
SHAPES = ["serve_batch", "serve_trinit"]
SKIP_SHAPES: dict[str, str] = {}

# Production store geometry (per shard): P patterns × L_shard items.
N_PATTERNS = 1024
L_SHARD = 8192
N_ENTITIES = 250_000      # entities (the key domain) per shard
N_RELAX = 10
N_QUERIES = 32
T_MAX = 4
# seen_cap: §Perf iteration — bounds probe bytes/iteration (−29%); the
# validated frontier on the benchmark workload shows zero quality loss at
# cap ≈ N/1.05 and 1/20 queries deviating at N/1.4 (EXPERIMENTS.md §Perf).
ENGINE = EngineConfig(block=256, k=100, grid_bins=512, seen_cap=16384)


def config() -> EngineConfig:
    return ENGINE


def smoke_config() -> EngineConfig:
    return EngineConfig(block=16, k=5, grid_bins=128)


def store_specs(n_shards: int):
    i32, f32 = jnp.int32, jnp.float32
    Pn, L = N_PATTERNS, L_SHARD
    stores = TripleStore(
        keys=base.spec((n_shards, Pn, L), i32),
        scores=base.spec((n_shards, Pn, L), f32),
        lengths=base.spec((n_shards, Pn), i32),
        sorted_keys=base.spec((n_shards, Pn, L), i32),
        stats=base.spec((n_shards, Pn, 4), f32),
        # Adaptive signature width: the ingest sizes W from the longest
        # list (8k-item shards get 16k words — lists ≫ 2k keys/lane would
        # saturate the old fixed 1024-word default).
        sketch=base.spec((n_shards, Pn, sketches.SKETCH_LANES,
                          sketches.adaptive_words(L_SHARD)), jnp.uint32),
        # Exact planner's key bitmaps at the width the sharded ingest
        # gives: global key domain, global list length.
        key_bits=base.spec((n_shards, Pn, kg.bitmap_words(
            n_shards * N_ENTITIES, n_shards * L_SHARD)), jnp.uint32),
    )
    relax = RelaxTable(ids=base.spec((Pn, N_RELAX), i32),
                       weights=base.spec((Pn, N_RELAX), f32))
    gstats = base.spec((Pn, 4), f32)
    queries = base.spec((N_QUERIES, T_MAX), i32)
    return stores, relax, gstats, queries


def make_cell(shape: str) -> base.CellSpec:
    mode = "trinit" if shape == "serve_trinit" else "specqp"
    assert sharding.active(), "kg-specqp cells need an installed mesh"
    mesh = sharding._state.mesh
    axes = tuple(mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    stores, relax, gstats, queries = store_specs(n_shards)
    fn = dist.make_batched_sharded_fn(ENGINE, mode, mesh, axes)
    shard_ax = ("all_devices",)
    store_axes = TripleStore(
        keys=("all_devices", None, None), scores=("all_devices", None, None),
        lengths=("all_devices", None), sorted_keys=("all_devices", None, None),
        stats=("all_devices", None, None),
        sketch=("all_devices", None, None, None),
        key_bits=("all_devices", None, None))
    relax_axes = RelaxTable(ids=(None, None), weights=(None, None))
    return base.CellSpec(ARCH, shape, "serve", fn,
                         (stores, relax, gstats, queries),
                         (store_axes, relax_axes, (None, None),
                          (None, None)))


def smoke():
    """Single-device Spec-QP == TriniT-exactness smoke (tiny workload)."""
    import numpy as np
    from repro.data import kg_synth
    from repro.core import engine
    wl = kg_synth.tiny_workload(seed=0, n_queries=4)
    cfg = smoke_config()
    outs = []
    for i in range(len(wl.queries)):
        q = jnp.asarray(wl.queries[i])
        rt = engine.run_query(wl.store, wl.relax, q, cfg, "trinit")
        rs = engine.run_query(wl.store, wl.relax, q, cfg, "specqp")
        bk, bs = engine.naive_full_scan(wl.store, wl.relax, q, cfg.k,
                                        wl.n_entities)
        assert np.allclose(np.asarray(bs), np.asarray(rt.scores),
                           rtol=1e-5), i
        outs.append((rt, rs))
    return outs
