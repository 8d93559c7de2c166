"""chip_smoke.py's phases at the tiny test geometry, on the CPU.

The script itself refuses to run anywhere but a TPU; these tests call its
phase functions directly so that its answer checks (TriniT against the
oracle, serving against run_query, Pallas against jnp, sharded against
the single-device oracle) are exercised on every test run.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from conftest import TEST_GRID_BINS, TEST_LIST_LEN, TEST_N_ENTITIES
from repro.core.types import EngineConfig

ROOT = Path(__file__).resolve().parents[1]
CFG = EngineConfig(block=16, k=5, grid_bins=TEST_GRID_BINS)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture(scope="module")
def wl():
    return cs.ingest_phase(0, list_len=TEST_LIST_LEN, n_relax=3,
                           n_entities=TEST_N_ENTITIES)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(argv, monkeypatch, capsys):
    monkeypatch.setattr(cs.compile_cache, "enable", lambda: None)
    with pytest.raises(SystemExit) as exc:
        cs.main(argv)
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_ingest_builds_xkg_queries(wl):
    assert len(wl.queries) >= 32
    assert wl.queries.shape[1] == 4     # xkg: 2-4 patterns per query


def test_sequential_phase_checks_trinit_against_oracle(wl):
    """The oracle check runs uncapped even when the configuration caps the
    seen ring (a capped TriniT may lose answers; it is only reported)."""
    out = cs.sequential_phase(wl, dataclasses.replace(CFG, seen_cap=32),
                              n_queries=3)
    assert 0.0 < out["precision"] <= 1.0
    assert 0.0 < out["trinit_capped_precision"] <= 1.0


def test_serving_phase_equals_run_query(wl):
    served = cs.serving_phase(wl, CFG, n_queries=8, lanes=4, depth=8)["served"]
    assert len(served) == 8


def test_pallas_phase_equals_jnp_and_kernel_check_fires(wl):
    text = cs.pallas_phase(wl, CFG, n_queries=2)
    # Off a TPU the kernel is interpreted, so the compiled-kernel check
    # must refuse this program.
    with pytest.raises(AssertionError):
        cs.assert_kernel_compiled(text)


def test_answer_check_catches_a_wrong_answer(wl, monkeypatch):
    """A serving answer that differs from run_query fails the phase."""
    real = cs.batching.BatchExecutor.run

    def corrupt(self, queries):
        out = real(self, queries)
        r = out[0]
        out[0] = dataclasses.replace(r, scores=r.scores * 0.5)
        return out

    monkeypatch.setattr(cs.batching.BatchExecutor, "run", corrupt)
    with pytest.raises(AssertionError):
        cs.serving_phase(wl, CFG, n_queries=4, lanes=4, depth=4)


def test_sharded_phase_on_four_cpu_devices():
    """The --chips 4 path on four virtual CPU devices (own process: the
    device count is fixed before JAX starts)."""
    script = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.core.types import EngineConfig
        cfg = EngineConfig(block=16, k=5, grid_bins={TEST_GRID_BINS})
        wl = cs.ingest_phase(0, list_len={TEST_LIST_LEN}, n_relax=3,
                             n_entities={TEST_N_ENTITIES})
        cs.sharded_phase(wl, cfg, n_shards=4, n_queries=3)
        print("SHARDED_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stdout + out.stderr
    line = [s for s in out.stdout.splitlines() if s.startswith("[sharded]")]
    assert "trinit_equals_oracle=True" in line[0]
