"""Every part scope of the planner and the executor reaches the compiled
programs' ``op_name`` metadata, under the names ``repro.core.scopes``
gives; the benchmark's trace reduction reads them from there."""
import re

import jax.numpy as jnp
import pytest

from conftest import small_workload, TEST_GRID_BINS
from repro.core import engine, scopes
from repro.core.types import EngineConfig, PAD_KEY

CFG = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)
# A dotted layer name inside an op_name, e.g. vmap(planner.cardinality).
NAMED = re.compile(r"\b(?:planner|executor)\.[a-z_]+")


def _op_names(compiled_text: str) -> set[str]:
    return {m for name in re.findall(r'op_name="([^"]*)"', compiled_text)
            for m in NAMED.findall(name)}


def _queue(wl, m):
    t = max(len(q) for q in wl.queries)
    out = jnp.full((m, t), PAD_KEY, jnp.int32)
    return out.at[:len(wl.queries[:m])].set(jnp.asarray(wl.queries[:m]))


@pytest.mark.parametrize("mode", ["exact", "sketch"])
def test_planner_program_names_every_planner_part(mode):
    wl = small_workload(seed=0, n_queries=4)
    cfg = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS,
                       cardinality_mode=mode)
    text = engine.plan_query_batch.lower(
        wl.store, wl.relax, _queue(wl, 4), cfg, "specqp").compile().as_text()
    assert _op_names(text) == set(scopes.PLANNER)


def test_executor_program_names_every_executor_part():
    wl = small_workload(seed=0, n_queries=4)
    queue = _queue(wl, 4)
    masks = engine.plan_query_batch(wl.store, wl.relax, queue, CFG, "specqp")
    # Two lanes over four queries: the refill splice is compiled in.
    text = engine.run_query_stream_with_masks.lower(
        wl.store, wl.relax, queue, masks, CFG, 2).compile().as_text()
    assert _op_names(text) == set(scopes.EXECUTOR)


def test_scope_names_are_distinct_parts_of_their_layer():
    names = scopes.PLANNER + scopes.EXECUTOR
    assert len(set(names)) == len(names) == 9
    assert all(NAMED.fullmatch(n) for n in names)
    assert all(n.startswith("planner.") for n in scopes.PLANNER)
    assert all(n.startswith("executor.") for n in scopes.EXECUTOR)
