"""Read the numbers that ``correct`` compares, over many seeds in one
process: for the program as the configuration states it, and for the
control, which serves the same queries from scores rounded to a lower
precision while the reference keeps the configuration's.

    python3 bench/control.py --workload twitter.steady --seeds 11,12,13 \
        --seconds 20 [--score-dtype bfloat16]

Each seed is one run of the cell (its arrival rate, its pool, its checks)
with a window of ``--seconds``; set-up is paid per seed but compiles only
once. One line per seed, then the widest and narrowest readings. The
limits in the configuration files were set from these readings
(``PERF.md``); the benchmark's own runs never run the control.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--score-dtype", default=None)
    args = ap.parse_args(argv)
    from bench import cell

    c = cell.load_cell(args.workload)
    readings = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = cell.run(c, seed, args.seconds, False, time.perf_counter(),
                       score_dtype=args.score_dtype)
        row = {"seed": seed, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               **{k: v["value"] for k, v in out["checks"].items()},
               **{k: v["value"] for k, v in out["metrics"].items()}}
        print("[seed] " + json.dumps(row), flush=True)
        for k in out["checks"]:
            readings.setdefault(k, []).append(out["checks"][k]["value"])
    print(json.dumps({"workload": args.workload,
                      "score_dtype": args.score_dtype,
                      **{k: {"max": max(v), "min": min(v)}
                         for k, v in readings.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
