"""Chip benchmark: serve one cell of BENCHMARK.json through
``ClusterEngine.serve`` on a TPU and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine whose chips the cell asks
for.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics read from a profiler trace of the window.  The last
line of stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, and ``checks`` last); the compared numbers and
their limits are also the last lines of stderr.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  ``--keep-trace PATH`` copies the raw trace of a ``--trace 1`` run
to PATH.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.resolve(args.workload)
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, keep_trace=args.keep_trace)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
