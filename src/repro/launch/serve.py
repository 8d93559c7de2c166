"""Serving launcher: micro-batched KG query serving (the paper's workload).

``python -m repro.launch.serve --dataset xkg_mini --mode specqp --k 10``
loads (generates) a workload and serves it through the micro-batching
layer (``repro.launch.batching``): requests are queued, padded into shape
buckets, answered by the unified executor — in its continuous-refill
streaming configuration by default; ``--no-refill`` selects the
fixed-batch (lanes = batch) configuration — and unpadded, reporting
QPS + latency percentiles + the wasted-iteration fraction against the
sequential one-query-at-a-time baseline. ``--arrival-qps`` replays the
workload as a Poisson arrival process through the threaded MicroBatcher
(latency then includes queue wait); the default is offline max-throughput
mode. DESIGN.md §8 documents the layer.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.types import EngineConfig
from repro.data import kg_synth
from repro.launch import batching, compile_cache


def sequential_baseline(wl, cfg, mode, queries):
    """One run_query per request (the pre-batching serving loop)."""
    q0 = jnp.asarray(queries[0])
    jax.block_until_ready(
        engine.run_query(wl.store, wl.relax, q0, cfg, mode).scores)
    lat = []
    t_start = time.perf_counter()
    for q in queries:
        t0 = time.perf_counter()
        res = engine.run_query(wl.store, wl.relax, jnp.asarray(q), cfg, mode)
        jax.block_until_ready(res.scores)
        lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_start
    return wall, np.asarray(lat)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="xkg_mini",
                    choices=["xkg_mini", "twitter_mini"])
    ap.add_argument("--mode", default="specqp",
                    choices=["specqp", "specqp_pattern", "trinit",
                             "join_only"])
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--grid-bins", type=int, default=256)
    ap.add_argument("--list-len", type=int, default=512)
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--refill", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="continuous-refill streaming configuration of the "
                         "unified executor (the default): finished lanes "
                         "are spliced with queued queries instead of "
                         "freezing until the batch tail; --no-refill "
                         "serves fixed micro-batches (lanes = batch)")
    ap.add_argument("--lanes", type=int, default=None,
                    help="device lanes for --refill (default: max-batch)")
    ap.add_argument("--refill-depth", type=int, default=64,
                    help="admission-queue entries per streaming call")
    ap.add_argument("--pipeline", action="store_true",
                    help="overlap planning of group i+1 with execution of "
                         "group i (offline mode)")
    ap.add_argument("--arrival-qps", type=float, default=None,
                    help="replay as a Poisson arrival process through the "
                         "threaded MicroBatcher (default: offline batches)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # Fail bad knobs at the CLI boundary with argparse's usage message
    # (BatchingConfig re-validates with ValueError for library callers).
    if args.lanes is not None and args.lanes < 1:
        ap.error(f"--lanes must be >= 1, got {args.lanes}")
    if args.refill_depth < 1:
        ap.error(f"--refill-depth must be >= 1, got {args.refill_depth}")
    if args.max_batch < 1:
        ap.error(f"--max-batch must be >= 1, got {args.max_batch}")

    wl = kg_synth.make_workload(args.dataset, list_len=args.list_len,
                                n_queries=args.n_queries, seed=args.seed)
    cfg = EngineConfig(block=args.block, k=args.k,
                       grid_bins=args.grid_bins)
    queries = [np.asarray(q) for q in wl.queries]
    t_set = sorted({int((q >= 0).sum()) for q in queries})

    q_buckets = tuple(sorted({b for b in (1, 4, 16, 64)
                              if b <= args.max_batch} | {args.max_batch}))
    bcfg = batching.BatchingConfig(
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms * 1e-3,
        q_buckets=q_buckets, t_buckets=tuple(t_set),
        refill=args.refill, lanes=args.lanes,
        refill_depth=args.refill_depth, pipeline=args.pipeline)
    ex = batching.BatchExecutor(wl.store, wl.relax, cfg, args.mode, bcfg)
    n_compiled = ex.warmup()
    extra = (f" refill(lanes={ex._lanes_n()}, depth={bcfg.refill_depth})"
             if args.refill else "")
    print(f"{args.dataset} mode={args.mode} k={args.k}: "
          f"{len(queries)} queries | warmed {n_compiled} "
          f"(q_bucket × t_bucket) jit specializations "
          f"q={bcfg.q_buckets} t={bcfg.t_buckets}{extra}"
          f"{' pipeline' if args.pipeline else ''}")

    seq_wall, seq_lat = sequential_baseline(wl, cfg, args.mode, queries)
    print(f"  sequential: {len(queries) / seq_wall:7.1f} QPS | "
          f"p50 {np.percentile(seq_lat, 50) * 1e3:6.1f}ms "
          f"p99 {np.percentile(seq_lat, 99) * 1e3:6.1f}ms")

    if args.arrival_qps:
        rng = np.random.default_rng(args.seed)
        gaps = rng.exponential(1.0 / args.arrival_qps, size=len(queries))
        # Latency = submit → future resolution (recorded by a done
        # callback in the worker thread, not when the collection loop
        # happens to reach the future).
        done_t = np.zeros(len(queries))

        def _mark(i):
            return lambda _f: done_t.__setitem__(i, time.perf_counter())

        with batching.MicroBatcher(ex) as mb:
            futs, t_sub = [], []
            t_start = time.perf_counter()
            for i, (q, gap) in enumerate(zip(queries, gaps)):
                time.sleep(gap)
                t_sub.append(time.perf_counter())
                f = mb.submit(q)
                f.add_done_callback(_mark(i))
                futs.append(f)
            for f in futs:
                f.result()
            wall = time.perf_counter() - t_start
        lat = done_t - np.asarray(t_sub)
        label = f"online λ={args.arrival_qps:g}/s"
    else:
        t_start = time.perf_counter()
        ex.run(queries)
        wall = time.perf_counter() - t_start
        # Offline latency = completion time of the request's micro-batch
        # plus its amortized share of the plan phase (same accounting as
        # benchmarks.paper_tables, and comparable to the sequential
        # baseline, whose run_query times include planning).
        plan_amort = ex.plan_total_s / max(len(queries), 1)
        lat = np.asarray([s.exec_s + plan_amort for s in ex.stats
                          for _ in range(s.n_requests)])
        label = "batched    "
    mean_b = np.mean([s.n_requests for s in ex.stats]) if ex.stats else 0
    print(f"  {label}: {len(queries) / wall:7.1f} QPS | "
          f"p50 {np.percentile(lat, 50) * 1e3:6.1f}ms "
          f"p99 {np.percentile(lat, 99) * 1e3:6.1f}ms | "
          f"speedup {seq_wall / wall:4.2f}x | mean batch {mean_b:.1f} | "
          f"wasted-iter frac {ex.wasted_fraction():.3f}")


if __name__ == "__main__":
    main()
