"""A whole run on the CPU with the look for a chip skipped: the result
line's schema, and the refusals of ``bench/run.py``."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

CELLS = [("dedup-k128.lookup", False), ("dedup-k128.lookup", True),
         ("dedup-k128.ingest", False), ("dedup-k128.ingest", True)]


def run(name, **kw):
    return harness.run_cell(tiny.cell(name), seed=kw.pop("seed", 2 ** 32 + 3),
                            seconds=kw.pop("seconds", 1.0),
                            traced=kw.pop("traced", False),
                            t_start=time.perf_counter(),
                            log=lambda msg: None, **kw)


@pytest.mark.parametrize("name,traced", CELLS)
def test_last_line_schema(name, traced):
    line = json.loads(json.dumps(run(name, traced=traced)))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    c = tiny.cell(name)
    entries = c.per_layer if traced else c.end_to_end
    # on the CPU the device-trace readers find nothing and stay silent
    assert set(line["metrics"]) == {m["name"] for m in entries
                                    if m["source"] != "device_trace"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and np.isfinite(v["value"])
    assert line["compared"]
    for v in line["compared"].values():
        assert set(v) == {"value", "limit"} and v["value"] <= v["limit"]
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert dev["window_s"] > 0 and "breakdown" in line
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _bench_cmd(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dedup-k128.lookup",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _bench_cmd(tiny.ROOT, env)
    assert r.returncode != 0
    assert "needs a tpu device" in r.stderr
    assert not r.stdout.strip()


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _bench_cmd(str(tmp_path), env)
    assert r.returncode != 0
    assert "repro" in r.stderr
    assert not r.stdout.strip()
