"""BENCHMARK.json against the contract's shape, and discovery by name."""

import json
import os
import re
import shutil

import pytest

from bench import manifest
from bench.tests.tiny import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def test_manifest_keys_and_names(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    names += [c["name"] for c in man["configs"]]
    names += [w["name"] for w in man["workloads"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_resolves_and_reports(man):
    for w in man["workloads"]:
        c = manifest.cell(ROOT, w["name"])
        assert w["chips"] in (1, 4)
        e2e = {m["name"] for m in c.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert c.per_layer, w["name"]
        for m in c.per_layer:      # what a per-layer metric moves is here
            assert m["moves"] in e2e
        for m in c.end_to_end + c.per_layer:
            assert callable(manifest.reader(m["name"]))


def test_config_files_state_their_cuts(man):
    for conf in man["configs"]:
        assert conf["file"].startswith("bench/")
        with open(os.path.join(ROOT, conf["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == conf["name"]
        assert cfg["source"] == conf["source"]
        assert set(conf["reduced"]) == set(cfg["reduced"])
        assert cfg["n_bands"] * cfg["rows_per_band"] == cfg["k"]


def test_a_cell_added_through_files_alone(tmp_path, man):
    """A new configuration, traffic mix and metric are found by name from
    new files and new manifest entries; no existing file changes."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "bench/configs/dedup-k128.json")) as f:
        cfg = dict(json.load(f), name="dummy-k64", k=64, n_bands=8)
    (bench_dir / "configs" / "dummy-k64.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "query_stream", "phases": [{"rate_qps": 5, "ms": 1000}],
         "max_batch": 2, "max_delay_ms": 1.0, "depth": 1,
         "query_edit_tokens": 1}))
    (bench_dir / "metrics" / "answered.dummy.py").write_text(
        "def read(ctx):\n    return ctx['attempted'] - ctx['failed']\n")
    new = json.loads(json.dumps(man))
    new["configs"].append({"name": "dummy-k64", "source": "x",
                           "file": "bench/configs/dummy-k64.json",
                           "reduced": [], "why": "test"})
    new["workloads"].append({"name": "dummy-k64.trickle",
                             "config": "dummy-k64", "traffic": "trickle",
                             "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "answered.dummy", "unit": "queries",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "query_p50_ms",
                             "workloads": ["dummy-k64.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    c = manifest.cell(str(tmp_path), "dummy-k64.trickle",
                      bench_dir=str(bench_dir))
    assert c.config["k"] == 64 and c.traffic["max_batch"] == 2
    assert [m["name"] for m in c.per_layer] == ["answered.dummy"]
    got = manifest.read_metrics(c.per_layer, {"attempted": 7, "failed": 2},
                                bench_dir=str(bench_dir))
    assert got == {"answered.dummy": {"value": 5.0, "unit": "queries"}}
    with pytest.raises(KeyError):
        manifest.cell(ROOT, "dummy-k64.trickle")


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    entries = [{"name": "query_p50_ms", "unit": "ms"},
               {"name": "ingest_docs_per_s", "unit": "docs/s"}]
    ctx = {"kind": "ingest", "lat_ms": None, "window_s": 2.0,
           "docs_in_window": 10}
    assert manifest.read_metrics(entries, ctx) == {
        "ingest_docs_per_s": {"value": 5.0, "unit": "docs/s"}}
