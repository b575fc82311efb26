"""Run one benchmark cell on the chip and print its result.

    python3 chipbench/run.py --workload search.ranked_top10 --seed 7 \
        --seconds 10 --trace 0

Run from the root of a checkout (``BENCHMARK.json`` beside ``chipbench/``
and the program under ``src/``). It makes its data from ``--seed``, builds
and warms up the system (``setup_s``), measures for ``--seconds``, checks
what the window produced against a plain reference, and prints one JSON
object as the last line of standard output. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` records a profiler trace of the
window and reports the per-layer metrics instead. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run.py: the program is missing: no {src}/repro in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from chipbench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
