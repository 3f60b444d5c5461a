"""Readings that the limit of a cell's comparison is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: set the cell up as a run does, serve a
short window at the cell's own load, free the program's state, and
compare the sampled served scores with the reference (``score_gap``, the
program's reading) and the reference computed in bfloat16 in the
program's place with the float32 reference (``control_gap``, the
control's reading).  Prints one JSON line per seed.  Needs a TPU.
"""
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, weights
    cell = harness.resolve(args.workload)
    harness.device_info(cell.chips)
    harness.configure_jax(cell)
    runner = (harness.run_open if cell.traffic["kind"] == "open"
              else harness.run_backlog)
    with harness.CompileCounter() as compiles:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            engine = harness.build_engine(cell,
                                          weights.make(cell.config, seed))
            payloads = harness.Payloads(cell, seed)
            harness.warm_up(engine, payloads, cell, compiles)
            win = runner(engine, payloads, cell, seed, args.seconds)
            engine = None
            gc.collect()
            got = harness.check(cell, seed, win, payloads, control=True)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "requests": len(win.done), **got,
                              "wall_s": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
