"""The control of ``correct``, at a cell's own size on the chip.

    python3 bench/control.py --workload dedup-k128.lookup --b 8 \\
        --seeds 11,12,13 --seconds 10

Runs the cell on each seed with the program's own lower-precision path
switched on: ``b`` stored bits per code where the configuration states
exact 32-bit codes.  Prints one JSON line per seed with the numbers the
check compared and whether the run came out correct; a sound control reads
not correct on every seed.  Not part of a benchmark run: the benchmark's
runs never switch the path on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--b", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness, manifest
    from bench.run import log, require_chips
    from repro.launch.compile_cache import setup_compile_cache
    cell = manifest.cell(ROOT, args.workload)
    require_chips(cell.chips)
    setup_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               traced=False, t_start=time.perf_counter(),
                               b=args.b, log=log)
        print(json.dumps({"workload": args.workload, "b": args.b,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
