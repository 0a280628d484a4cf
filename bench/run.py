"""The chip benchmark: one run of one cell, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell (``BENCHMARK.json``'s ``workloads``) names its configuration
file and traffic mix; ``bench/harness.py`` builds it, warms it, measures
for ``--seconds``, and checks the window's answers against the plain
reference.  ``--trace 1`` records a profiler trace of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``compared``: each number the check compared with its limit.  The same
numbers end standard error.  A run whose JAX finds no TPU, or fewer chips
than the cell asks for, exits non-zero and prints no result.
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

REQUIRED_PLATFORM = "tpu"


def require_chips(n: int):
    """The devices to run on; exits when JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        raise SystemExit(f"bench: needs a {REQUIRED_PLATFORM} device; JAX "
                         f"found platform {devices[0].platform!r}")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell asks for {n} chips; JAX found "
                         f"{len(devices)}")
    return devices


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, manifest
    from repro.launch.compile_cache import setup_compile_cache
    cell = manifest.cell(ROOT, args.workload)
    require_chips(cell.chips)
    log(f"compile cache: {setup_compile_cache()}")
    out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           traced=bool(args.trace), t_start=T_START, log=log)
    log("info: " + json.dumps(out["info"]))
    for name, c in out["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
