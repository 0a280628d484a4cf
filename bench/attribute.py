"""One run of one cell, with what the harness keeps to itself: the device
idle time under each program span, the window's counters and the Python
collector's pauses.

    python3 bench/attribute.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--keep-trace <path.xplane.pb.gz>]

Runs the cell as ``bench/run.py`` does (``--trace 1`` by default) and prints
the same result line, with the cell's end-to-end metrics added to
``metrics`` when traced and, under ``info``:

* ``idle_by_span`` and ``long_idle_gaps`` (``bench/spans.py``; traced only);
* the window's ``query.brute_rows`` and ``query.spill_rows`` counters;
* ``gc_window``: the collections the window held per generation, and the
  longest of them as ``[generation, ms, start in s from the window's]``.

``--keep-trace`` writes the window's profiler trace, gzipped, to the path
given.  Chip only, like ``run.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import gzip                                                  # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

COUNTERS = ("query.brute_rows", "query.spill_rows")


class CollectorLog:
    """Start, duration and generation of every collection while entered."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._t0 = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def __enter__(self) -> "CollectorLog":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def window(self, t0: float, seconds: float, top: int = 5) -> dict:
        inside = [(t - t0, d, g) for t, d, g in self.pauses
                  if t0 <= t <= t0 + seconds]
        longest = sorted(inside, key=lambda p: -p[1])[:top]
        return {"collections": [sum(g == i for _, _, g in inside)
                                for i in range(3)],
                "longest": [[g, d * 1e3, at] for at, d, g in longest]}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from bench import harness, manifest, spans
    from bench import trace as btrace
    from bench.run import log, require_chips
    from repro.launch.compile_cache import setup_compile_cache
    cell = manifest.cell(ROOT, args.workload)
    require_chips(cell.chips)
    log(f"compile cache: {setup_compile_cache()}")

    # the harness hands on neither its raw trace nor its Run: both are
    # caught on their way through, and the harness itself runs unchanged
    extra: dict = {}
    runs: list = []
    reduce0, kind = harness._reduce, cell.traffic["kind"]
    run0 = harness.KINDS[kind]

    def reduce_and_attribute(rec):
        # harness._reduce, with the trace read once for both reductions
        if rec.get("path") is None:
            return reduce0(rec)
        try:
            ev = btrace.load(rec["path"])
            extra.update(spans.attribute(ev, spans.load(rec["path"])))
            if args.keep_trace:
                with open(rec["path"], "rb") as src, \
                        gzip.open(args.keep_trace, "wb") as dst:
                    shutil.copyfileobj(src, dst)
            return btrace.reduce(ev)
        finally:
            btrace.cleanup(rec)

    def run_and_keep(*a, **kw):
        runs.append(run0(*a, **kw))
        return runs[-1]

    kinds = harness.KINDS
    harness._reduce = reduce_and_attribute
    harness.KINDS = dict(kinds, **{kind: run_and_keep})
    try:
        with CollectorLog() as collector:
            out = harness.run_cell(cell, seed=args.seed,
                                   seconds=args.seconds,
                                   traced=bool(args.trace), t_start=T_START,
                                   log=log)
    finally:
        harness._reduce, harness.KINDS = reduce0, kinds
    ctx = runs[0].ctx()
    if args.trace:
        out["metrics"].update(manifest.read_metrics(cell.end_to_end, ctx))
    counters = ctx["delta"].get("counters", {})
    out["info"].update(extra, **{n: counters.get(n, 0) for n in COUNTERS})
    out["info"]["gc_window"] = collector.window(T_START + ctx["setup_s"],
                                                ctx["window_s"])
    log("info: " + json.dumps(out["info"]))
    for name, c in out["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
