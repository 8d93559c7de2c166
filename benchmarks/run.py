"""Benchmark entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]`` prints
``name,us_per_call,derived`` CSV rows plus the markdown report, appends
the report to results/paper_report.md, and appends the CSV rows (with a
run-stamp header) to results/benchmark_rows.csv so the CI artifact
carries the machine-readable history too. Roofline rows (if dry-run
results exist) are summarized at the end.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced workloads (CI-sized)")
    args, _ = ap.parse_known_args()

    from benchmarks import paper_tables

    t0 = time.time()
    report, results, plan_rows, serve_rows, refill_rows = \
        paper_tables.run_all(fast=args.fast)
    dt = time.time() - t0

    # CSV contract: name,us_per_call,derived. Rows are printed AND kept
    # for results/benchmark_rows.csv (the CI artifact).
    csv_rows: list[str] = []

    def emit(line: str) -> None:
        csv_rows.append(line)
        print(line)

    print("name,us_per_call,derived")
    for ds, res in results.items():
        for k, rows in res.items():
            t_tr = np.mean([r["t_trinit"] for r in rows]) * 1e6
            t_sp = np.mean([r["t_specqp"] for r in rows]) * 1e6
            prec = np.mean([r["prec"] for r in rows])
            pull_ratio = (np.mean([r["pulled_t"] for r in rows]) /
                          max(np.mean([r["pulled_s"] for r in rows]), 1))
            emit(f"table2_precision_{ds}_k{k},{t_sp:.0f},{prec:.3f}")
            emit(f"fig6_runtime_trinit_{ds}_k{k},{t_tr:.0f},1.0")
            emit(f"fig6_runtime_specqp_{ds}_k{k},{t_sp:.0f},"
                  f"{t_tr/max(t_sp,1e-9):.2f}")
            emit(f"fig6_pull_ratio_{ds}_k{k},{t_sp:.0f},{pull_ratio:.2f}")
            # per-relaxation (T,R) plan vs the per-pattern ablation: mean
            # pulls of Spec-QP relative to the coarser plan (≤ 1.0 expected)
            pp = np.mean([r["pulled_pp"] for r in rows])
            sp = np.mean([r["pulled_s"] for r in rows])
            emit(f"fig6_perrelax_vs_pattern_pull_{ds}_k{k},{t_sp:.0f},"
                  f"{sp / max(pp, 1):.3f}")
            prec_pp = np.mean([r["prec_pp"] for r in rows])
            emit(f"table2_precision_patternplan_{ds}_k{k},{t_sp:.0f},"
                  f"{prec_pp:.3f}")
            acc_rows = [r for r in rows]
            exact = np.mean([r["plan_exact"] for r in acc_rows])
            emit(f"table3_prediction_{ds}_k{k},{t_sp:.0f},{exact:.3f}")
            err = np.mean([r["err_mean"] for r in rows])
            emit(f"table4_score_err_{ds}_k{k},{t_sp:.0f},{err:.4f}")
    for r in plan_rows:
        # derived = plan-time share of execute-time (flat in L for sketch).
        emit(f"plan_cost_exact_L{r['L']},{r['plan_exact']*1e6:.0f},"
              f"{r['plan_exact']/max(r['exec'],1e-9):.3f}")
        emit(f"plan_cost_sketch_L{r['L']},{r['plan_sketch']*1e6:.0f},"
              f"{r['plan_sketch']/max(r['exec'],1e-9):.3f}")
        emit(f"plan_mask_agreement_L{r['L']},{r['plan_sketch']*1e6:.0f},"
              f"{r['agree']:.3f}")
    for r in serve_rows:
        # Default executor rows: the unified loop's continuous-refill
        # configuration (lanes sweep over a depth-64 admission queue).
        # us_per_call = per-request p50 latency; derived varies per row.
        tag = "seq" if r["lanes"] == 0 else f"lanes{r['lanes']}"
        emit(f"serving_qps_{tag},{r['p50']*1e6:.0f},{r['qps']:.1f}")
        emit(f"serving_p99_{tag},{r['p99']*1e6:.0f},{r['p99']*1e3:.2f}")
        emit(f"serving_speedup_{tag},{r['p50']*1e6:.0f},"
              f"{r['speedup']:.2f}")
        emit(f"serving_wasted_{tag},{r['p50']*1e6:.0f},{r['wasted']:.3f}")
        emit(f"serving_topk_match_{tag},{r['p50']*1e6:.0f},"
              f"{r['match']:.3f}")
    for r in refill_rows:
        # Continuous-refill streaming vs fixed micro-batches (skewed
        # stream); the acceptance metric is serving_refill_wasted_refill
        # strictly below serving_refill_wasted_fixed.
        tag = r["variant"]
        emit(f"serving_refill_qps_{tag},{r['p50']*1e6:.0f},{r['qps']:.1f}")
        emit(f"serving_refill_p99_{tag},{r['p99']*1e6:.0f},"
             f"{r['p99']*1e3:.2f}")
        emit(f"serving_refill_wasted_{tag},{r['p50']*1e6:.0f},"
             f"{r['wasted']:.4f}")
        emit(f"serving_refill_topk_match_{tag},{r['p50']*1e6:.0f},"
             f"{r['match']:.3f}")

    print(report)
    os.makedirs("results", exist_ok=True)
    # Append (never clobber) so the perf history survives across runs.
    stamp = time.strftime("%Y-%m-%d %H:%M:%S")
    profile = "fast" if args.fast else "full"
    with open("results/paper_report.md", "a") as f:
        f.write(f"\n\n## Benchmark run {stamp} ({profile} profile)\n")
        f.write(report + f"\n\n(total bench time {dt:.0f}s)\n")
    with open("results/benchmark_rows.csv", "a") as f:
        f.write(f"# run {stamp} ({profile} profile)\n")
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(csv_rows) + "\n")

    # Roofline summary if dry-run results exist.
    try:
        from benchmarks import roofline
        rows = roofline.load_results()
        if rows:
            print("\n### Dry-run/roofline summary")
            print(roofline.summarize(rows))
    except Exception as e:  # noqa: BLE001
        print(f"(roofline summary unavailable: {e})", file=sys.stderr)


if __name__ == "__main__":
    main()
