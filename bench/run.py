"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
Earlier lines give the set-up, the store's bytes, the generator's lateness
and the compiles counted inside the window; the compared numbers are also
the last lines of standard error. Off a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import cell
    c = cell.load_cell(args.workload)
    out = cell.run(c, args.seed, args.seconds, bool(args.trace), T_PROC)
    print(json.dumps(out), flush=True)
    for name, chk in out["checks"].items():
        print(f"{name} {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr, flush=True)
    # Leave at once: nothing may print after the checks, and an executor
    # call abandoned past the drain must not hold the exit.
    os._exit(0)

if __name__ == "__main__":
    sys.exit(main())
