"""Compiles of the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX, so it refuses here what the chip
would refuse: a Pallas block that breaks the (8, 128) tiling rule, a program
that does not fit the device's 16 GB. Nothing runs, so these tests say
nothing about answers or times. The topology is described inside a fixture
(never while a module is imported), so every pytest-xdist worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import kg_specqp
from repro.core import engine, kg, sketches
from repro.core.types import RelaxTable, TripleStore
from repro.kernels import ops as kops
from repro.kernels import rank_join

V5E_HBM_BYTES = 16 * 10**9
LANES, DEPTH, T = 16, 64, kg_specqp.T_MAX


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an entry
    compiled for an absent chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store_relax(sharding):
    """Shapes of one kg_specqp shard: P patterns × L items, R relaxations."""
    Pn, L, R = kg_specqp.N_PATTERNS, kg_specqp.L_SHARD, kg_specqp.N_RELAX
    i32, f32 = jnp.int32, jnp.float32
    store = TripleStore(
        keys=_spec(sharding, (Pn, L), i32),
        scores=_spec(sharding, (Pn, L), f32),
        lengths=_spec(sharding, (Pn,), i32),
        sorted_keys=_spec(sharding, (Pn, L), i32),
        stats=_spec(sharding, (Pn, 4), f32),
        sketch=_spec(sharding, (Pn, sketches.SKETCH_LANES,
                                sketches.adaptive_words(L)), jnp.uint32),
        key_bits=_spec(sharding, (Pn, kg.bitmap_words(
            kg_specqp.N_ENTITIES, L)), jnp.uint32))
    relax = RelaxTable(ids=_spec(sharding, (Pn, R), i32),
                       weights=_spec(sharding, (Pn, R), f32))
    return store, relax


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


def test_rank_join_kernel_compiles_under_lane_and_stream_vmap(one_chip):
    """The engine vmaps the probe over lanes and streams; the kernel must
    stay one Mosaic call with the seen count as an SMEM scalar."""
    N, B = 16384, 256
    lookup = jax.vmap(jax.vmap(
        lambda k, s, p, c: rank_join.rank_join_lookup(k, s, p, c,
                                                      interpret=False)))
    args = (_spec(one_chip, (LANES, T, N), jnp.int32),
            _spec(one_chip, (LANES, T, N), jnp.float32),
            _spec(one_chip, (LANES, T, B), jnp.int32),
            _spec(one_chip, (LANES, T), jnp.int32))
    text = jax.jit(lookup).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("use_pallas", [False, True])
def test_run_query_compiles_at_kg_specqp_geometry(one_chip, use_pallas,
                                                  monkeypatch):
    # The dispatch asks the default backend, which is the CPU here: tell
    # it the program is for a TPU, as it would see on the chip.
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    store, relax = _store_relax(one_chip)
    cfg = dataclasses.replace(kg_specqp.ENGINE, use_pallas=use_pallas)
    compiled = engine.run_query.lower(
        store, relax, _spec(one_chip, (T,), jnp.int32), cfg=cfg,
        mode="specqp").compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    assert _bytes(compiled) < V5E_HBM_BYTES


def test_refill_stream_fits_one_v5e(one_chip):
    """Plan + refill stream over a 64-deep queue on 16 lanes (serve.py's
    defaults): arguments plus temporaries fit one chip's HBM."""
    store, relax = _store_relax(one_chip)
    compiled = engine.run_query_stream.lower(
        store, relax, _spec(one_chip, (DEPTH, T), jnp.int32),
        cfg=kg_specqp.ENGINE, mode="specqp", lanes=LANES).compile()
    assert _bytes(compiled) < V5E_HBM_BYTES


def test_batched_exact_planner_fits_one_v5e(one_chip):
    """The exact planner over a 64-deep queue of 4-pattern queries counts
    from the key bitmaps (a popcount in the program) and, with the store,
    fits one chip's HBM."""
    store, relax = _store_relax(one_chip)
    compiled = engine.plan_query_batch.lower(
        store, relax, _spec(one_chip, (DEPTH, T), jnp.int32),
        cfg=kg_specqp.ENGINE, mode="specqp").compile()
    assert "popcnt" in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM_BYTES
