"""Knee sweep for a query cell: one process, one set-up, several fixed rates.

    python3 bench/sweep.py --workload search-k256.stream --seed 7 \\
        --seconds 5 --rates 8000,9000,10000,11000,12000

Builds the cell's index once, then offers each rate open-loop for
``--seconds`` through a fresh stream front with the cell's batching, and
prints one JSON line per rate: offered and completed queries per second,
p50 and p95 latency from due time, the p95 of the window's first and last
thirds (a backlog that grows shows as a rising last third), and how late
the generator ran.  The knee is the highest rate whose completions keep up
with the offer and whose last third does not climb; the cells' traffic
files carry fractions of it as numbers.  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated queries per second")
    args = ap.parse_args(argv)
    import numpy as np

    from bench import gen, harness, manifest
    from bench.run import log, require_chips
    from repro.launch.compile_cache import setup_compile_cache
    cell = manifest.cell(ROOT, args.workload)
    require_chips(cell.chips)
    setup_compile_cache()
    cfg, tr = cell.config, cell.traffic
    rates = [float(r) for r in args.rates.split(",")]
    ks = gen.keys(args.seed)
    dues = [gen.arrival_offsets([{"rate_qps": r, "ms": 1000}], args.seconds,
                                ks["numpy"] + i)
            for i, r in enumerate(rates)]
    n_total = sum(len(d) for d in dues)
    svc, _, qrows, info = harness.setup_query_index(
        cfg, tr, ks, n_total, cfg["b"], log)
    log(f"setup: {time.perf_counter() - T_START:.3f} s {json.dumps(info)}")
    lo = 0
    harness.steady_heap()
    for rate, due in zip(rates, dues):
        rows = qrows[lo: lo + len(due)]
        lo += len(due)
        with svc.stream(max_batch=tr["max_batch"],
                        max_delay_ms=tr["max_delay_ms"], depth=tr["depth"],
                        top_k=cfg["top_k"]) as stream:
            b0 = stream.n_batches
            with harness.CompileMeter() as meter:
                got = harness.open_loop(stream, rows, due, args.seconds,
                                        cfg["top_k"])
            n_batches = stream.n_batches - b0
        done, due_abs, late = got["done"], got["due"], got["late"]
        ok = ~np.isnan(done)
        lat = (done - due_abs)[ok] * 1e3
        third = len(due) // 3
        span = np.nanmax(done) - due_abs[0]
        print(json.dumps({
            "workload": args.workload, "rate_qps": rate,
            "offered": len(due), "answered": int(ok.sum()),
            "completed_qps": float(ok.sum() / span),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_third_ms": float(np.percentile(
                (done - due_abs)[:third][ok[:third]] * 1e3, 95)),
            "p95_last_third_ms": float(np.percentile(
                (done - due_abs)[-third:][ok[-third:]] * 1e3, 95)),
            "batch_fill": float(ok.sum() / max(n_batches, 1)),
            "late_p50_ms": float(np.percentile(late, 50) * 1e3),
            "late_p99_ms": float(np.percentile(late, 99) * 1e3),
            "compiles": meter.n}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
