# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV
# and writes the machine-readable BENCH_sign.json signing-path artifact.
# ``--smoke`` (CI): 1 warmup / 1 iter / tiny shapes — exercises every script
# end-to-end without timing flakiness; numbers are not comparable.
import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)                       # `import benchmarks` as a script
sys.path.insert(0, os.path.join(_ROOT, "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 warmup, 1 iter, tiny shapes (CI regression mode)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.smoke:
        from benchmarks import common
        common.set_smoke(True)

    from benchmarks import (bench_dedup, bench_etilde, bench_mae, bench_ratio,
                            bench_search, bench_sign, bench_throughput,
                            bench_variance, common)
    smoke = common.smoke()
    print("name,us_per_call,derived")
    bench_variance.run(n_rep=2_000 if smoke else 60_000)  # Fig 6
    bench_etilde.run()       # Fig 2, 3: Var vs J; E~ monotone (Lemma 3.3)
    bench_ratio.run()        # Fig 4, 5: variance ratios / Prop 3.5
    bench_mae.run(**({"n_docs": 8, "n_reps": 2} if smoke else {}))  # Fig 7
    bench_throughput.run()   # §5: throughput + K->2 memory
    bench_dedup.run(n_docs=24 if smoke else 120)   # production dedup pipeline
    search_rows = bench_search.run(   # store vs dict + sharded plane
        **({"n_items": 2_000, "n_queries": 16,
            "ingest_docs": 1_000, "ingest_batch": 128} if smoke else {}))
    sign_rows = bench_sign.run()   # signing hot path (kernel dispatch)

    # smoke numbers are not comparable: never clobber the tracked artifacts
    suffix = ".smoke.json" if smoke else ".json"
    for stem, rows in (("BENCH_sign", sign_rows),
                       ("BENCH_search", search_rows)):
        out = os.path.join(_ROOT, stem + suffix)
        with open(out, "w") as f:
            json.dump({"smoke": smoke, "rows": rows}, f, indent=1)
        print(f"# wrote {out}")


if __name__ == '__main__':
    main()
