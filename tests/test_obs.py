"""Observability plane: exact snapshot algebra, wire-propagated traces,
STATS snapshots, dump files, and the disabled fast path.

The design contract under test mirrors ``merge_topk``'s: per-process
measurements reduce to a global view with an exact, associative,
commutative merge — S shard snapshots combined in any order or grouping
produce identical bytes.  Histogram sums are integer nanos, so this is
provable equality, not approximate.  The trace test spawns a REAL tcp
shard worker and asserts the coordinator and worker spans of one query
share a trace id (the stitched sign->shard->serve trace).
"""

import json
import os
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.dump import MetricsDumper, check_dump

K, NB, R = 64, 16, 4


# -- histogram merge: exact, associative, commutative -------------------------

@settings(max_examples=30)
@given(st.data())
def test_hist_merge_exact_over_random_shard_splits(data):
    """Observing a stream into one histogram == splitting it across S
    'shard' histograms and merging the snapshots, in ANY order/grouping."""
    seed = data.draw(st.integers(0, 2**31 - 1), "seed")
    s = data.draw(st.integers(2, 5), "shards")
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 200))
    values = rng.uniform(0.0, 10.0, n) ** 3        # spans many buckets
    owner = rng.integers(0, s, n)

    whole = obs_metrics.Histogram("h")
    parts = [obs_metrics.Histogram("h") for _ in range(s)]
    for v, o in zip(values, owner):
        whole.observe(float(v))
        parts[int(o)].observe(float(v))
    snaps = [{"hists": {"h": p.to_snapshot()}} for p in parts]

    want = whole.to_snapshot()
    # any permutation: commutativity
    perm = rng.permutation(s)
    merged = obs_metrics.merge_snapshots(*[snaps[i] for i in perm])
    assert merged["hists"]["h"] == want
    # any grouping: associativity (left fold vs split-merge)
    cut = int(rng.integers(1, s)) if s > 1 else 1
    left = obs_metrics.merge_snapshots(*snaps[:cut])
    right = obs_metrics.merge_snapshots(*snaps[cut:])
    assert obs_metrics.merge_snapshots(left, right)["hists"]["h"] == want


def test_merge_counters_gauges_and_quantiles():
    reg_a, reg_b = obs_metrics.Registry(), obs_metrics.Registry()
    reg_a.counter("c").inc(3)
    reg_b.counter("c").inc(4)
    reg_a.gauge("g").set(10)
    reg_b.gauge("g").set(5)
    for v in (0.001, 0.002, 0.004, 0.1):
        reg_a.histogram("h").observe(v)
    merged = obs_metrics.merge_snapshots(reg_a.snapshot(), reg_b.snapshot())
    assert merged["counters"]["c"] == 7
    assert merged["gauges"]["g"] == 15          # gauges are summable levels
    h = merged["hists"]["h"]
    assert h["count"] == 4
    # bucket-resolution quantiles: ~19% relative error band
    assert obs_metrics.hist_quantile(h, 0.5) == pytest.approx(0.002, rel=0.3)
    assert obs_metrics.hist_quantile(h, 1.0) == pytest.approx(0.1, rel=0.3)
    assert obs_metrics.hist_sum(h) == pytest.approx(0.107, rel=1e-6)


def test_quantiles_interpolate_within_one_bucket():
    """Regression: when one log bucket holds all the mass, p50/p90/p99 used
    to collapse to the same bucket edge — three identical numbers carrying
    one bucket of information.  Interpolation places them at their
    fractional ranks, so they spread monotonically inside the bucket and
    stay within its edges."""
    h = obs_metrics.Histogram("h")
    h.observe_n(0.0015, 100)                 # single-bucket mass
    p50, p90, p99 = (h.quantile(q) for q in (0.5, 0.9, 0.99))
    assert p50 < p90 < p99                   # distinct, monotone
    for p in (p50, p90, p99):                # within ~one bucket of truth
        assert p == pytest.approx(0.0015, rel=0.3)
    # snapshot-form quantiles agree with the live object
    snap = h.to_snapshot()
    assert obs_metrics.hist_quantile(snap, 0.9) == pytest.approx(p90)
    # underflow bucket interpolates linearly from 0; q=0 sits at its floor
    lo = obs_metrics.Histogram("lo")
    lo.observe_n(0.0, 10)
    assert 0.0 <= lo.quantile(0.5) <= lo.quantile(0.99)
    assert h.quantile(0.0) <= p50


def test_snapshot_delta_scopes_a_window():
    reg = obs_metrics.Registry()
    reg.counter("c").inc(5)
    reg.histogram("h").observe(0.5)
    before = reg.snapshot()
    reg.counter("c").inc(2)
    reg.histogram("h").observe(0.25)
    delta = obs_metrics.snapshot_delta(before, reg.snapshot())
    assert delta["counters"] == {"c": 2}
    assert delta["hists"]["h"]["count"] == 1
    assert obs_metrics.hist_sum(delta["hists"]["h"]) == \
        pytest.approx(0.25, rel=1e-9)


# -- the disabled fast path ---------------------------------------------------

def test_disabled_registry_is_noop_and_cheap():
    """Null instruments are shared singletons, record nothing, and cost
    well under a microsecond per call — the 'observability off' contract
    (the enabled-vs-disabled wall-clock delta is tracked by the
    search_obs_overhead row in bench_search, not asserted here)."""
    reg = obs_metrics.Registry(enabled=False)
    c = reg.counter("a")
    assert c is reg.counter("b") is obs_metrics.NULL_COUNTER
    assert reg.histogram("a") is obs_metrics.NULL_HISTOGRAM
    assert reg.gauge("a") is obs_metrics.NULL_GAUGE
    c.inc(10**6)
    reg.histogram("a").observe(1.0)
    reg.gauge("a").set(5.0)
    assert reg.snapshot() == obs_metrics.empty_snapshot()

    n = 50_000
    h = reg.histogram("x")
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
        h.observe_n(2.0, 3)
    per_op = (time.perf_counter() - t0) / (2 * n)
    assert per_op < 5e-6, f"null instrument op cost {per_op * 1e9:.0f}ns"


def test_timer_without_profiler_session_is_cheap():
    """The span helper on a disabled registry and an unsampled tracer, with
    no profiler session: no annotation is made, the span is the shared
    no-op, and enter + exit stays a few microseconds."""
    reg = obs_metrics.Registry(enabled=False)
    t = obs_trace.Timer("query.fold", reg, obs_trace.Tracer(sample_rate=0.0))
    with t as span:
        assert span is obs_trace.NULL_SPAN
    assert t.last >= 0.0 and reg.snapshot() == obs_metrics.empty_snapshot()

    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with t:
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 10e-6, f"timer cost {per_span * 1e9:.0f}ns"


def test_timer_feeds_histogram_span_and_last():
    reg = obs_metrics.Registry()
    tr = obs_trace.Tracer(sample_rate=1.0, proc="t")
    outer = obs_trace.Timer("store.query", reg, tr)
    inner = obs_trace.Timer("query.fold", reg, tr)
    with outer as root:
        root.tag("n", 3)
        with inner:
            time.sleep(0.002)
    assert inner.last >= 0.002 and outer.last >= inner.last
    snap = reg.snapshot()["hists"]
    assert snap["query.fold"]["count"] == snap["store.query"]["count"] == 1
    assert obs_metrics.hist_sum(snap["query.fold"]) == \
        pytest.approx(inner.last, abs=1e-9)
    spans = {s["name"]: s for s in tr.drain()}
    assert spans["query.fold"]["parent"] == spans["store.query"]["span"]
    assert spans["store.query"]["tags"] == {"n": 3}
    # an exception still closes the leg and is not swallowed
    with pytest.raises(KeyError):
        with inner:
            raise KeyError("x")
    assert reg.snapshot()["hists"]["query.fold"]["count"] == 2


def test_obs_imports_and_times_without_jax():
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro.obs import Timer, Registry\n"
            "t = Timer('query.fold', Registry())\n"
            "with t: pass\n"
            "assert 'jax.profiler' not in sys.modules\n"
            "print(t.last >= 0)\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60,
                       env=dict(os.environ, PYTHONPATH=src))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == "True"


# -- dump files ---------------------------------------------------------------

def test_metrics_dumper_and_checker(tmp_path):
    path = str(tmp_path / "dump.jsonl")
    reg = obs_metrics.Registry()
    tr = obs_trace.Tracer(sample_rate=1.0, proc="t")
    with MetricsDumper(path, interval_s=0.05, registry=reg, tracer=tr):
        reg.counter("events").inc(3)
        reg.histogram("query.shard0.partial").observe(0.01)
        reg.histogram("query.shard1.partial").observe(0.02)
        with tr.span("op"):
            pass
        time.sleep(0.15)            # at least one periodic line
    out = check_dump(path, require_shard_hists=True)
    assert out["lines"] >= 2        # periodic + final
    assert out["spans"] == 1        # spans are incremental: exactly once
    assert out["shard_hists"] == ["query.shard0.partial",
                                  "query.shard1.partial"]


def test_dump_checker_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"t": 1, "seq": 0}\n')
    with pytest.raises(ValueError, match="missing"):
        check_dump(str(bad))
    empty_hists = tmp_path / "nohists.jsonl"
    empty_hists.write_text(json.dumps(
        {"t": 1, "seq": 0, "spans": [],
         "metrics": obs_metrics.empty_snapshot()}) + "\n")
    check_dump(str(empty_hists))    # well-formed without the shard gate
    with pytest.raises(ValueError, match="per-shard"):
        check_dump(str(empty_hists), require_shard_hists=True)


def test_dump_checker_overload_families(tmp_path):
    """``--require-overload`` passes only when retry-budget, breaker, and
    a shedding surface are all wired — worker metrics folded in by an
    ``extra`` callable (per-lane relabelled STATS snapshots) count."""
    reg = obs_metrics.Registry()
    reg.gauge("transport.retry_budget.tokens").set(100.0)
    reg.gauge("transport.breaker.127.0.0.1:9000.state").set(0.0)
    partial = tmp_path / "partial.jsonl"
    partial.write_text(json.dumps(
        {"t": 1, "seq": 0, "spans": [], "metrics": reg.snapshot()}) + "\n")
    with pytest.raises(ValueError, match="shed_surface"):
        check_dump(str(partial), require_overload=True)

    # the shedding surface arrives via a worker STATS snapshot the dump's
    # ``extra`` callable folded in, not the coordinator registry
    wreg = obs_metrics.Registry()
    wreg.gauge("shard0.replica1.worker.admission.depth").set(0.0)
    wreg.counter("shard0.replica1.worker.overloaded").inc()
    full = tmp_path / "full.jsonl"
    full.write_text(json.dumps(
        {"t": 1, "seq": 0, "spans": [], "metrics": reg.snapshot(),
         "workers": {"shard0.replica1": wreg.snapshot()}}) + "\n")
    out = check_dump(str(full), require_overload=True)
    assert set(out["overload_families"]) == {"retry_budget", "breaker",
                                             "shed_surface"}


# -- wire-propagated traces + STATS snapshots (real tcp workers) --------------

def test_trace_and_stats_roundtrip_through_tcp_workers():
    """One sampled query over a 2-shard tcp plane yields ONE trace whose
    spans cover the coordinator AND both worker processes; worker STATS
    carries a parseable registry snapshot, and obs_snapshot() folds the
    plane into one view with nonzero per-shard partial histograms."""
    from repro.store import ShardedSketchStore, StoreConfig
    from repro.transport import connect_sharded, shutdown_plane, spawn_workers

    rng = np.random.default_rng(3)
    sigs = rng.integers(0, 1 << 16, (80, K), dtype=np.int32)
    cfg = StoreConfig(k=K, n_bands=NB, rows_per_band=R)
    tracer = obs_trace.default()
    old_rate = tracer.sample_rate
    tracer.sample_rate = 1.0
    tracer.drain()                  # a clean ring for last_trace_id()
    handles = spawn_workers(cfg, 2)
    try:
        tcp = connect_sharded([h.address for h in handles], cfg, timeout=60)
        tcp.add(sigs)
        before = obs_metrics.default().snapshot()
        ids, _ = tcp.query(sigs[:6], top_k=3)
        assert np.array_equal(ids[:, 0], np.arange(6))   # sane answers

        tid = tracer.last_trace_id()
        assert tid is not None
        spans = tracer.for_trace(tid)
        procs = {s["proc"] for s in spans}
        assert {"shard0", "shard1"} <= procs, procs      # worker legs
        assert any(s["proc"] not in ("shard0", "shard1") for s in spans)
        assert {s["name"] for s in spans} >= \
            {"query.fold", "query.broadcast", "query.partial", "query.merge",
             "worker.query"}
        # every span of the trace shares the one id (they're from for_trace,
        # but check the worker spans' parents point into this trace too)
        by_id = {s["span"] for s in spans}
        for s in spans:
            if s["proc"].startswith("shard"):
                assert s["parent"] in by_id, "worker span not stitched"

        # per-shard partial latency histograms observed on the coordinator
        delta = obs_metrics.snapshot_delta(before,
                                           obs_metrics.default().snapshot())
        for i in range(2):
            assert delta["hists"][f"query.shard{i}.partial"]["count"] > 0

        # worker STATS carries its own registry snapshot ("obs"), tagged
        # with the shard index, and obs_snapshot() merges the plane
        for i, sh in enumerate(tcp.shards):
            st_ = sh.stats()
            assert st_["shard"] == i
            snap = json.loads(st_["obs"])
            assert set(snap) == {"counters", "gauges", "hists"}
            assert snap["hists"]["worker.handle.query"]["count"] > 0
            assert snap["counters"]["worker.bytes_in"] > 0
        plane = tcp.obs_snapshot()
        assert plane["hists"]["worker.handle.query"]["count"] >= 2
        shutdown_plane(tcp, handles)
    finally:
        tracer.sample_rate = old_rate
        for h in handles:
            h.terminate()
