"""Bring-up smoke run of the Spec-QP query service on TPU chips.

    python chip_smoke.py             # one chip: ingest, sequential, serving, Pallas
    python chip_smoke.py --chips 4   # four chips: the hash-partitioned store only,
                                     # four times the lists, one kg_specqp shard each

Drives the main path through the entry points a user calls, at the
production geometry of ``configs/kg_specqp.py`` (list length 8192, ten
relaxations per pattern, k = 100, block 256, seen_cap 16384) on an ``xkg``
workload generated from ``--seed``. Every phase prints one line; every
answer check raises on a mismatch, so any failure exits non-zero. The last
line of standard output is the JSON verdict
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Off a TPU the run stops at the device phase with a non-zero exit code.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch import compile_cache  # noqa: E402  (needs src on the path)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.monitoring import (register_event_duration_secs_listener,  # noqa: E402
                            register_event_listener)

from repro.configs import kg_specqp  # noqa: E402
from repro.core import distributed, engine  # noqa: E402
from repro.data import kg_synth  # noqa: E402
from repro.launch import batching  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

# Workload geometry: kg_specqp's per-shard list length and relaxation
# fan-out; enough entities that no list holds more than ~3% of them.
LIST_LEN = kg_specqp.L_SHARD
N_RELAX = kg_specqp.N_RELAX
N_ENTITIES = kg_specqp.N_ENTITIES
ENGINE = kg_specqp.ENGINE
N_SEQUENTIAL = 8        # queries answered one at a time (trinit + specqp)
N_SERVED = 32           # queries through the refill BatchExecutor
SERVE_LANES = 16        # launch/serve.py's default --max-batch
SERVE_DEPTH = 64        # launch/serve.py's default --refill-depth
N_SHARDS = 4

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, how often the
    backend compiled, and the persistent cache's hits and misses, read
    from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        register_event_duration_secs_listener(self._on_duration)
        register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.backend_compiles += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event: str, **_) -> None:
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self.cache_misses += event == "/jax/compilation_cache/cache_misses"


def check(ok: bool, what: str) -> None:
    """An answer or placement check; unlike ``assert`` it survives -O."""
    if not ok:
        raise AssertionError(what)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def device_phase(min_count: int = 1) -> dict:
    """Refuse anything but a TPU with at least ``min_count`` chips."""
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {info['platform']}")
    if info["count"] < min_count:
        raise SystemExit(f"need {min_count} chips, JAX sees {info['count']}")
    return info


def ingest_phase(seed: int, list_len: int = LIST_LEN, n_relax: int = N_RELAX,
                 n_entities: int = N_ENTITIES) -> kg_synth.KGWorkload:
    """Generate the xkg workload and place its store on the default device."""
    t0 = time.perf_counter()
    wl = kg_synth.make_workload("xkg", seed=seed, n_entities=n_entities,
                                list_len=list_len, n_relax=n_relax)
    jax.block_until_ready((wl.store, wl.relax))
    build_s = time.perf_counter() - t0
    leaves = jax.tree_util.tree_leaves((wl.store, wl.relax))
    dev = jax.devices()[0]
    check(all(x.devices() == {dev} for x in leaves), "store is not on device")
    lens = np.asarray(wl.store.lengths)
    say("ingest", patterns=len(lens), queries=len(wl.queries),
        list_len=list_len, n_entities=n_entities,
        max_list_share=float(lens.max() / n_entities),
        device_bytes=sum(x.nbytes for x in leaves), build_s=build_s)
    return wl


def _precision(keys: np.ndarray, oracle_keys: np.ndarray) -> float:
    want = set(oracle_keys[oracle_keys >= 0].tolist())
    got = set(keys[keys >= 0].tolist())
    return len(want & got) / max(len(want), 1)


def sequential_phase(wl, cfg, n_queries: int = N_SEQUENTIAL) -> dict:
    """run_query one query at a time against the naive_full_scan oracle.

    TriniT with an uncapped seen ring must equal the oracle. Under the
    configuration's ``seen_cap`` TriniT pulls past the ring and can lose
    answers whose partner was evicted (ROADMAP D6), so it and Spec-QP are
    reported as precision against the oracle. One query runs with sketched
    cardinalities.
    """
    exact = dataclasses.replace(cfg, seen_cap=None)
    prec, prec_capped, iters, t0 = [], [], [], time.perf_counter()
    for i in range(n_queries):
        q = jnp.asarray(wl.queries[i])
        ok, os_ = engine.naive_full_scan(wl.store, wl.relax, q, cfg.k,
                                         wl.n_entities)
        ok = np.asarray(ok)
        tri = engine.run_query(wl.store, wl.relax, q, exact, "trinit")
        np.testing.assert_allclose(np.asarray(tri.scores), np.asarray(os_),
                                   rtol=1e-5, err_msg=f"trinit query {i}")
        capped = engine.run_query(wl.store, wl.relax, q, cfg, "trinit")
        prec_capped.append(_precision(np.asarray(capped.keys), ok))
        spec = engine.run_query(wl.store, wl.relax, q, cfg, "specqp")
        prec.append(_precision(np.asarray(spec.keys), ok))
        iters.append(int(spec.n_iters))
    sk = engine.run_query(wl.store, wl.relax, jnp.asarray(wl.queries[0]),
                          dataclasses.replace(cfg, cardinality_mode="sketch"),
                          "specqp")
    sk_scores = np.asarray(sk.scores)
    check(np.isfinite(sk_scores).any(), "sketch run returned no answer")
    say("sequential", queries=n_queries, trinit_uncapped_matches_oracle=True,
        trinit_capped_precision=float(np.mean(prec_capped)),
        specqp_precision=float(np.mean(prec)), specqp_iters=iters,
        sketch_answers=int(np.isfinite(sk_scores).sum()),
        wall_s=time.perf_counter() - t0)
    return {"precision": float(np.mean(prec)),
            "trinit_capped_precision": float(np.mean(prec_capped))}


def serving_phase(wl, cfg, n_queries: int = N_SERVED,
                  lanes: int = SERVE_LANES, depth: int = SERVE_DEPTH) -> dict:
    """Serve queries through the refill BatchExecutor, built as
    launch/serve.py builds it except for one T bucket; each answer equals
    its run_query answer.

    serve.py buckets T by the true pattern counts, so every count runs its
    own admission queue and drains its own lanes. On one v5e these 32
    queries had not been served after about 500 s that way (one queue:
    under 300 s), so here every query is padded to the widest T and one
    queue serves them all.
    """
    queries = [np.asarray(q) for q in wl.queries[:n_queries]]
    t_max = max(int((q >= 0).sum()) for q in queries)
    bcfg = batching.BatchingConfig(
        max_batch=lanes, q_buckets=tuple(sorted({1, 4, lanes})),
        t_buckets=(t_max,), refill=True, lanes=None, refill_depth=depth)
    ex = batching.BatchExecutor(wl.store, wl.relax, cfg, "specqp", bcfg)
    t0 = time.perf_counter()
    served = ex.run(queries)
    serve_s = time.perf_counter() - t0
    for i, (q, r) in enumerate(zip(queries, served)):
        ref = engine.run_query(wl.store, wl.relax, jnp.asarray(q), cfg,
                               "specqp")
        np.testing.assert_array_equal(r.keys, np.asarray(ref.keys),
                                      err_msg=f"served query {i} keys")
        np.testing.assert_array_equal(r.scores, np.asarray(ref.scores),
                                      err_msg=f"served query {i} scores")
    # What the compiler allots the planner at the queue's shape, to set
    # beside the allocator's peak in the report.
    plan_mem = engine.plan_query_batch.lower(
        wl.store, wl.relax, jnp.zeros((ex._m_bucket(n_queries), t_max),
                                      jnp.int32),
        cfg=cfg, mode="specqp").compile().memory_analysis()
    say("serving", queries=n_queries, lanes=ex._lanes_n(),
        depth=bcfg.refill_depth, t_buckets=bcfg.t_buckets,
        equals_run_query=True, serve_s=serve_s,
        wasted_fraction=ex.wasted_fraction(),
        planner_temp_bytes=plan_mem.temp_size_in_bytes)
    return {"served": served}


def pallas_phase(wl, cfg, n_queries: int = N_SEQUENTIAL) -> str:
    """run_query with the Pallas rank-join probe equals the jnp path.
    Returns the compiled program's text for ``assert_kernel_compiled``."""
    cfg_p = dataclasses.replace(cfg, use_pallas=True)
    q0 = jnp.asarray(wl.queries[0])
    text = engine.run_query.lower(wl.store, wl.relax, q0, cfg=cfg_p,
                                  mode="specqp").compile().as_text()
    for i in range(n_queries):
        q = jnp.asarray(wl.queries[i])
        want = engine.run_query(wl.store, wl.relax, q, cfg, "specqp")
        got = engine.run_query(wl.store, wl.relax, q, cfg_p, "specqp")
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(want.keys),
                                      err_msg=f"pallas query {i} keys")
        np.testing.assert_allclose(np.asarray(got.scores),
                                   np.asarray(want.scores), rtol=1e-6,
                                   err_msg=f"pallas query {i} scores")
    say("pallas", queries=n_queries, equals_jnp=True,
        tpu_custom_calls=text.count("tpu_custom_call"))
    return text


def assert_kernel_compiled(hlo_text: str) -> None:
    check("tpu_custom_call" in hlo_text, "rank-join kernel was not compiled")


def pattern_lists(store) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per-pattern (keys, scores) host lists of a built store."""
    keys, scores = np.asarray(store.keys), np.asarray(store.scores)
    lens = np.asarray(store.lengths)
    return [(keys[p, :n], scores[p, :n]) for p, n in enumerate(lens)]


def sharded_phase(wl, cfg, n_shards: int = N_SHARDS,
                  n_queries: int = N_SEQUENTIAL) -> dict:
    """Hash-partition the store over ``n_shards`` devices (one shard each)
    and check the sharded entry points against the single-device oracle.

    ``wl`` holds ``n_shards`` times the per-shard lists, so that each shard
    holds what ``configs/kg_specqp.py`` gives one device. With an uncapped
    seen ring the sharded answers are exact: TriniT equals
    ``naive_full_scan`` on the unsharded store, and batched Spec-QP plans
    as the single-device planner does and equals the oracle restricted to
    that plan. Under the configuration's ``seen_cap`` the batched Spec-QP
    answers are reported as precision against the oracle, as the one-chip
    sequential phase reports them.
    """
    exact = dataclasses.replace(cfg, seen_cap=None)
    mesh = make_mesh((n_shards,), ("shard",),
                     devices=jax.devices()[:n_shards])
    t0 = time.perf_counter()
    skg = distributed.build_sharded_kg(pattern_lists(wl.store), wl.relax,
                                       n_shards, mesh=mesh)
    jax.block_until_ready(skg.stores)
    build_s = time.perf_counter() - t0
    shard_devs = [s.device for s in skg.stores.keys.addressable_shards]
    check(len(set(shard_devs)) == n_shards, f"shards on {shard_devs}")
    qs = jnp.asarray(wl.queries[:n_queries])
    oracle = [engine.naive_full_scan(wl.store, wl.relax, q, cfg.k,
                                     wl.n_entities) for q in qs]
    for i in range(n_queries):
        got = distributed.run_query_sharded(skg, qs[i], exact, "trinit",
                                            mesh)
        np.testing.assert_allclose(np.asarray(got.scores),
                                   np.asarray(oracle[i][1]), rtol=1e-5,
                                   err_msg=f"sharded trinit query {i}")
    masks = engine.plan_query_batch(wl.store, wl.relax, qs, cfg=cfg,
                                    mode="specqp")
    res = distributed.make_batched_sharded_fn(exact, "specqp", mesh)(
        skg.stores, skg.relax, skg.global_stats, qs)
    for i in range(n_queries):
        np.testing.assert_array_equal(np.asarray(res.relax_mask[i]),
                                      np.asarray(masks[i]),
                                      err_msg=f"sharded plan query {i}")
        _, want = engine.naive_full_scan(wl.store, wl.relax, qs[i], cfg.k,
                                         wl.n_entities, masks[i])
        np.testing.assert_allclose(np.asarray(res.scores[i]),
                                   np.asarray(want), rtol=1e-5,
                                   err_msg=f"batched sharded query {i}")
    capped = distributed.make_batched_sharded_fn(cfg, "specqp", mesh)(
        skg.stores, skg.relax, skg.global_stats, qs)
    prec = float(np.mean([_precision(np.asarray(capped.keys[i]),
                                     np.asarray(oracle[i][0]))
                          for i in range(n_queries)]))
    say("sharded", shards=n_shards, queries=n_queries,
        shard_list_len=int(skg.stores.keys.shape[-1]),
        bytes_per_device=sum(x.addressable_shards[0].data.nbytes
                             for x in jax.tree_util.tree_leaves(skg.stores)),
        trinit_equals_oracle=True, batched_specqp_equals_oracle=True,
        capped_specqp_precision=prec, build_s=build_s,
        wall_s=time.perf_counter() - t0)
    return {"capped_precision": prec}


def report_phase(clock: CompileClock, n_devices: int) -> None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n_devices]]
    say("report", compile_s=clock.seconds,
        backend_compiles=clock.backend_compiles,
        persistent_cache_hits=clock.cache_hits,
        persistent_cache_misses=clock.cache_misses, peak_bytes_in_use=peaks)


def main(argv: list[str] | None = None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, N_SHARDS), default=1,
                    help=f"{N_SHARDS}: run only the hash-partitioned store")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    clock = CompileClock()
    info = device_phase(args.chips)
    if args.chips == 1:
        wl = ingest_phase(args.seed)
        sequential_phase(wl, ENGINE)
        serving_phase(wl, ENGINE)
        assert_kernel_compiled(pallas_phase(wl, ENGINE))
    else:
        # The whole store of n shards: each shard's lists come out about
        # LIST_LEN long, over as many entities per shard as on one chip.
        wl = ingest_phase(args.seed, list_len=args.chips * LIST_LEN,
                          n_entities=args.chips * N_ENTITIES)
        sharded_phase(wl, ENGINE, args.chips)
    report_phase(clock, args.chips)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
