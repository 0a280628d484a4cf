"""Device idle time put down to the program's spans, and the per-layer
metrics that read the program's span histograms."""

import collections
import gc
import json
import os
import time

import pytest

from bench import attribute, harness, manifest, spans, trace
from bench.tests import tiny

MS = 1_000_000
DEV = "/device:TPU:0"


def _events():
    ev = trace.Events()
    ev.host = [("main", "bench.window", 0, 100 * MS)]
    ev.device = [(DEV, "p", "op", 0, 10 * MS),
                 (DEV, "p", "op", 30 * MS, 40 * MS),
                 (DEV, "p", "op", 95 * MS, 120 * MS)]
    return ev


def _spans():
    # the coalescer: a drain over [5, 60] holding a read over [10, 35] (the
    # device busy under it from 30), then 30 ms of nothing, then idle
    return [("t", "stream.drain", 5 * MS, 60 * MS),
            ("t", "query.readback", 10 * MS, 35 * MS),
            ("t", "stream.idle", 90 * MS, 200 * MS),
            ("t", "not.in.catalogue", 60 * MS, 90 * MS)]


def test_idle_goes_to_the_innermost_span():
    got = spans.attribute(_events(), [s for s in _spans()
                                      if s[1] in spans.catalogue()])
    by = {k: round(v * 1e3, 6) for k, v in got["idle_by_span"].items()}
    # idle: [10,30] under the read, [40,95]: drain 20, outside 30, idle 5
    assert by == {"query.readback": 20.0, "stream.drain": 20.0,
                  "outside": 30.0, "stream.idle": 5.0}
    assert sum(got["idle_by_span"].values()) == pytest.approx(0.075)
    # only the 55 ms gap reaches 50 ms; most of it lies outside any span
    assert got["long_idle_gaps"] == [["outside", 0.055, 0.040]]
    assert spans.attribute(_events(), [], min_gap_s=0.01) == {
        "idle_by_span": {"outside": 0.075},
        "long_idle_gaps": [["outside", 0.055, 0.04],
                           ["outside", 0.02, 0.01]]}


def test_idle_is_averaged_over_devices():
    ev = _events()
    ev.device += [("/device:TPU:1", "p", "op", 0, 100 * MS)]
    got = spans.attribute(ev, [])
    assert got["idle_by_span"] == {"outside": pytest.approx(0.0375)}


def test_catalogue_is_the_programs():
    from repro.obs.trace import SPANS
    assert spans.catalogue() == frozenset(SPANS)
    assert {"query.readback", "query.operands", "query.spill",
            "stream.idle", "ingest.wait"} <= spans.catalogue()


def _ctx(kind, hists, window_s=2.0):
    return {"kind": kind, "window_s": window_s,
            "delta": {"hists": {n: {"count": c, "sum_ns": int(s * 1e9)}
                                for n, (c, s) in hists.items()}}}


def test_readback_and_host_legs_per_batch():
    ctx = _ctx("query", {"query.wall": (10, 0.5), "query.readback": (30, 0.04),
                         "query.operands": (10, 0.006),
                         "query.spill": (10, 0.004)})
    assert manifest.reader("readback_ms.query")(ctx) == pytest.approx(4.0)
    assert manifest.reader("host_legs_ms.query")(ctx) == pytest.approx(1.0)
    # no spilled keys: the operands alone
    del ctx["delta"]["hists"]["query.spill"]
    assert manifest.reader("host_legs_ms.query")(ctx) == pytest.approx(0.6)


@pytest.mark.parametrize("name", ["readback_ms.query", "host_legs_ms.query",
                                  "wait_share.ingest"])
def test_readers_stay_silent_without_their_spans(name):
    for kind in ("query", "ingest"):
        assert manifest.reader(name)(_ctx(kind, {})) is None
    # the other kind of cell: silent too
    ctx = _ctx("ingest" if name.endswith(".query") else "query",
               {"query.wall": (1, 1.0), "query.readback": (1, 1.0),
                "query.operands": (1, 1.0), "ingest.wait": (1, 1.0)})
    assert manifest.reader(name)(ctx) is None


def test_wait_share_of_the_window():
    ctx = _ctx("ingest", {"ingest.wait": (20, 0.9)}, window_s=2.0)
    assert manifest.reader("wait_share.ingest")(ctx) == pytest.approx(45.0)


@pytest.mark.parametrize("name,traced", [("dedup-k128.lookup", 1),
                                         ("dedup-k128.ingest", 1),
                                         ("dedup-k128.lookup", 0)])
def test_attribute_run_on_the_cpu(monkeypatch, capsys, name, traced):
    """The whole of ``bench/attribute.py`` on a tiny cell, the look for a
    chip skipped: the result line gains the end-to-end metrics, the span
    attribution (nothing to attribute without a device plane), the
    window's counters and collections, and the harness is left as it was."""
    import bench.run
    from repro.launch import compile_cache
    monkeypatch.setattr(bench.run, "require_chips", lambda n: None)
    monkeypatch.setattr(compile_cache, "setup_compile_cache", lambda: "off")
    c = tiny.cell(name)
    monkeypatch.setattr(manifest, "cell", lambda root, n: c)
    kinds, reduce0 = harness.KINDS, harness._reduce
    assert attribute.main(["--workload", name, "--seed", str(2 ** 33 + 1),
                           "--seconds", "1", "--trace", str(traced)]) == 0
    assert harness.KINDS is kinds and harness._reduce is reduce0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    want = {m["name"] for m in c.end_to_end + (c.per_layer if traced else [])
            if m["source"] != "device_trace"}
    assert set(line["metrics"]) == want
    info = line["info"]
    if traced:
        assert info["idle_by_span"] == {"outside": 0.0}
        assert info["long_idle_gaps"] == []
    else:
        assert "idle_by_span" not in info
    assert info["query.brute_rows"] == 0
    assert info["query.spill_rows"] >= 0
    gcw = info["gc_window"]
    assert len(gcw["collections"]) == 3 and len(gcw["longest"]) <= 5
    for g, ms, at in gcw["longest"]:
        assert g in (0, 1, 2) and ms >= 0 and 0 <= at <= 1.5


def test_collector_log_keeps_the_windows_collections():
    with attribute.CollectorLog() as log:
        t0 = time.perf_counter()
        gc.collect()
    got = log.window(t0, 10.0)
    assert got["collections"] == [0, 0, 1]
    assert got["longest"][0][0] == 2 and got["longest"][0][2] >= 0
    assert log.window(t0 + 20.0, 1.0) == {"collections": [0, 0, 0],
                                          "longest": []}


def test_recorded_chip_trace():
    """A 0.83 s traced window of ``dedup-k128.lookup`` on one v5e, program
    spans and all: 61 batches, each leg once a batch on the coalescer's
    thread (the reads three times), and all but 0.4% of the idle time under
    a named span."""
    path = os.path.join(tiny.ROOT, "bench", "tests", "data",
                        "lookup_spans_v5e.xplane.pb.gz")
    ev, sp = trace.load(path), spans.load(path)
    red = trace.reduce(ev)
    assert red["window_s"] == pytest.approx(0.825618757, abs=1e-12)
    assert red["busy_s"] == pytest.approx(0.036826674, abs=1e-12)
    lo, hi = trace.window_of(ev)
    inside = [(ln, n) for ln, n, s, t in sp if lo <= s and t <= hi]
    assert len({ln for ln, _ in inside}) == 1
    count = collections.Counter(n for _, n in inside)
    assert count.pop("query.readback") == 3 * 61
    assert set(count.values()) == {61}
    got = spans.attribute(ev, sp)
    by = got["idle_by_span"]
    assert sum(by.values()) == pytest.approx(red["window_s"] - red["busy_s"],
                                             abs=1e-9)
    assert by["outside"] == pytest.approx(0.003162821, abs=1e-12)
    assert by["stream.dispatch"] == pytest.approx(0.272460473, abs=1e-12)
    assert by["query.readback"] == pytest.approx(0.22700781, abs=1e-12)
    assert list(by)[:2] == ["stream.dispatch", "query.readback"]
    assert got["long_idle_gaps"] == []
