import os
from functools import lru_cache

import pytest

# Tests run on the CPU (JAX_PLATFORMS=cpu): one real device, Pallas kernels
# in interpret mode. Only the dry-run forces 512 placeholder devices, in its
# own process; the chip is exercised by chip_smoke.py, not by pytest.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# ---------------------------------------------------------------------------
# Shared workload factory: ONE place that fixes the small test geometry.
# Shrinking G (histogram bins), L (list length) and the entity count here —
# and funneling every test through the same shapes so jit specializations
# are shared across modules — is what keeps the ~110-test fast profile
# inside the CI wall-clock budget (see .github/workflows/ci.yml).
# ---------------------------------------------------------------------------
TEST_GRID_BINS = 96      # planner histogram bins (G) for test configs
TEST_LIST_LEN = 48       # posting-list length (L) for synthetic stores
TEST_N_ENTITIES = 384


@lru_cache(maxsize=None)
def _cached_workload(seed, n_queries, n_entities, list_len, n_relax):
    from repro.data import kg_synth
    return kg_synth.tiny_workload(seed=seed, n_queries=n_queries,
                                  n_entities=n_entities, list_len=list_len,
                                  n_relax=n_relax)


def small_workload(seed=0, n_queries=8, n_entities=TEST_N_ENTITIES,
                   list_len=TEST_LIST_LEN, n_relax=3):
    """Cached small synthetic workload (shared across test modules)."""
    return _cached_workload(seed, n_queries, n_entities, list_len, n_relax)


@pytest.fixture(scope="session")
def wl_factory():
    return small_workload


# ---------------------------------------------------------------------------
# Trace-count probe (promoted from tests/test_speclint.py so every module
# can guard against retrace regressions): measures how many NEW jit
# specializations a block of calls compiles. jax's jitted callables expose
# the compiled-specialization count as the private ``fn._cache_size()``;
# the fixture hides that probe behind one seam so a jax upgrade only
# patches this spot.
# ---------------------------------------------------------------------------

@pytest.fixture
def jit_trace_growth():
    def growth(jitted_fn, *calls):
        """Run each zero-arg thunk in ``calls``; return how many NEW
        specializations ``jitted_fn`` compiled across them (0 = every
        call hit an existing specialization)."""
        import jax
        before = jitted_fn._cache_size()
        for call in calls:
            jax.block_until_ready(call())
        return jitted_fn._cache_size() - before
    return growth
