"""Find a cell's knee: the highest open-loop rate served without a growing
backlog, on the chip, in one process.

    python3 bench/sweep.py --workload twitter.steady --seed 5 \
        --rates 0.5,1,2,3 --seconds 20 [--call16]

Builds the cell's system once, with a pool large enough for every rate,
warms it as a run does, then offers each rate for ``--seconds`` as Poisson
arrivals of distinct queries, lowest rate first. Each rate
prints one line: answered rate, latency median and p95, the front end's
mean wait in the first and last third of the window (a backlog that grows
shows as a rising wait), queries per executor call, and plan and execute
time per call. It stops after the first rate that leaves more than two
queries, and more than a tenth, unanswered at the close. ``--call16`` then times one executor call
serving 16 queries of the widest T bucket. The knee goes into the cell's
traffic file by hand.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--call16", action="store_true")
    args = ap.parse_args(argv)
    from bench import cell, traffic

    c = cell.load_cell(args.workload)
    cell.device_info(c.chips)
    cell.enable_compile_cache()
    rng = np.random.default_rng([args.seed, 2])
    rates = [float(r) for r in args.rates.split(",") if r]
    scheds, start = [], 0
    for r in rates:
        s = traffic.schedule(dict(c.traffic, rate_qps=r, arrival="poisson"),
                             args.seconds, rng)
        scheds.append(traffic.Schedule(s.offsets, start + s.query, s.pool))
        start += s.pool
    pool = start + (16 if args.call16 else 0)
    # One pool serves every rate, so the store holds as many pattern slots
    # as the pool can fill, at least the configuration's.
    g = c.config["generator"]
    slots = pool * g["tp_range"][1] * (1 + g["n_relax"])
    config = dict(c.config, store_patterns=max(c.config["store_patterns"],
                                               slots))
    sys_ = cell.build_system(config, pool, args.seed)
    cell.say("setup", patterns=len(sys_.raw.patterns), pool=pool,
             store_bytes=sys_.store_bytes)
    cell.say("warm", programs=cell.warm(sys_),
             setup_s=time.perf_counter() - T_PROC)
    for r, s in zip(rates, scheds):
        w = cell.drive(sys_, s, args.seconds)
        ok = w.answered
        lat = (w.done[ok] - w.due[ok]) * 1e3
        start_of = {q: cl.start for cl in w.calls for q in cl.qids}
        wait = np.asarray([start_of.get(i, np.nan) - w.due[i]
                           for i in range(len(w.due))]) * 1e3
        third = len(wait) // 3
        answered = float(np.sum(w.done[ok] <= w.t0 + args.seconds))
        cell.say("rate", offered_qps=r, n=len(w.due),
                 answered_qps=answered / args.seconds,
                 p50_ms=cell.percentile(lat, 50),
                 p95_ms=cell.percentile(lat, 95),
                 wait_first_third_ms=float(np.nanmean(wait[:third])),
                 wait_last_third_ms=float(np.nanmean(wait[-third:])),
                 calls=len(w.calls),
                 queries_per_call=float(np.mean([len(x.qids)
                                                 for x in w.calls])),
                 plan_ms_per_call=float(np.mean([x.plan_s
                                                 for x in w.calls])) * 1e3,
                 exec_ms_per_call=float(np.mean([x.exec_s
                                                 for x in w.calls])) * 1e3,
                 pulled_mean=float(np.mean([x.n_pulled for x in w.results
                                            if x is not None])),
                 iters_mean=float(np.mean([x.n_iters for x in w.results
                                           if x is not None])))
        if answered < len(w.due) - max(2, 0.1 * len(w.due)):
            break
    if args.call16:
        qs = [np.array(q, np.int32) for q in sys_.raw.queries[-16:]]
        ex = sys_.executor
        ex.qid_of = {id(q): i for i, q in enumerate(qs)}
        t0 = time.perf_counter()
        ex.run_batch(qs)
        cell.say("call16", seconds=time.perf_counter() - t0,
                 t_bucket=ex.calls[-1].t_bucket,
                 plan_s=ex.calls[-1].plan_s, exec_s=ex.calls[-1].exec_s)
    print(json.dumps({"ok": True}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
