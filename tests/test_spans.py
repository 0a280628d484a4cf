"""The served paths' timers on a profiler trace: each leg's span appears on
the thread that drives it, nested under a parent the catalogue names, once
per batch; and the histograms, counters and timing splits the timers feed
keep their counts and keys."""

import collections
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.search import SearchConfig, SimilaritySearchService

D, K, NB, R = 1 << 12, 64, 16, 4
NNZ = 32


def _docs(n, seed=0, lo=0, hi=D // 2):
    rng = np.random.default_rng(seed)
    return np.sort(rng.integers(lo, hi, (n, NNZ), np.int32), axis=1)


def _service(n_shards, **kw):
    return SimilaritySearchService(SearchConfig(
        d=D, k=K, n_bands=NB, rows_per_band=R, n_shards=n_shards, **kw))


def _profile(tmp_path, body):
    """Run ``body`` under a profiler session; the program spans it wrote,
    as ``(host line, name, start_ns, end_ns)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            out += [(ln.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events if e.name in obs_trace.SPANS]
    return out


def _parents(events):
    """Each span's innermost enclosing span on its own line (None: none)."""
    out = []
    for line in {ln for ln, *_ in events}:
        stack = []
        for _, name, s, t in sorted((e for e in events if e[0] == line),
                                    key=lambda e: (e[2], -e[3])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            out.append((name, stack[-1][0] if stack else None))
            stack.append((name, t))
    return out


def _assert_nested(events):
    for name, parent in _parents(events):
        if obs_trace.SPANS[name]:
            assert parent in obs_trace.SPANS[name], (name, parent)
        else:
            assert parent is None, (name, parent)


def _hist_counts(before):
    delta = obs_metrics.snapshot_delta(before,
                                       obs_metrics.default().snapshot())
    return {n: h["count"] for n, h in delta["hists"].items()}


@pytest.mark.parametrize("s", [1, 2])
def test_stream_spans_nest_once_per_batch(tmp_path, s):
    svc = _service(s)
    docs = _docs(128, seed=1)
    svc.add_sparse(docs)
    got = {}

    def stream():
        with svc.stream(max_batch=8, max_delay_ms=1.0) as st:
            tickets = [st.submit_sparse(r) for r in docs[:20]]
            for t in tickets:
                t.result(timeout=60)
        got["batches"] = st.n_batches

    stream()                                # compile every batch shape
    before = obs_metrics.default().snapshot()
    events = _profile(tmp_path, stream)
    n = got["batches"]
    count = collections.Counter(name for _, name, *_ in events)
    # the coalescer thread drives every leg of the served query path
    assert len({ln for ln, *_ in events}) == 1
    for name in ("stream.dispatch", "stream.drain", "stream.resolve",
                 "store.query", "query.fold", "query.wall",
                 "query.broadcast", "query.partial", "query.merge"):
        assert count[name] == n, name
    assert count["stream.collect"] >= n
    assert count["query.operands"] == n * s         # one probe per shard
    # the fold's hashes, the query words, each shard's answers
    assert count["query.readback"] == n * (2 + s)
    assert "query.brute" not in count and "query.spill" not in count
    _assert_nested(events)
    # every span is its histogram's observation, one for one
    hists = _hist_counts(before)
    for name, c in count.items():
        assert hists[name] == c, name


def test_ingest_spans_nest_once_per_batch(tmp_path):
    svc = _service(1)
    docs = _docs(96, seed=2)
    np.asarray(svc._sign(docs[:16], "sparse"))

    def ingest():
        with svc.pipeline(depth=2) as pipe:
            for lo in range(0, len(docs), 16):
                pipe.submit(docs[lo: lo + 16])

    before = obs_metrics.default().snapshot()
    events = _profile(tmp_path, ingest)
    count = collections.Counter(name for _, name, *_ in events)
    assert len({ln for ln, *_ in events}) == 1
    assert count == {"ingest.sign": 6, "ingest.wait": 6, "ingest.scatter": 6,
                     "ingest.wall": 7}              # 6 submits + the flush
    _assert_nested(events)
    assert _hist_counts(before) == dict(count)


def test_histograms_counters_and_splits_keep_their_counts():
    """Ingest and a query batch with a brute-fallback row, on a fresh
    registry: each histogram counts what it counted before the timers, and
    the timing splits keep their keys."""
    reg = obs_metrics.Registry()
    old = obs_metrics.set_default(reg)
    try:
        svc = _service(2)
        docs = _docs(64, seed=3)
        with svc.pipeline(depth=2) as pipe:
            for lo in range(0, 64, 16):
                pipe.submit(docs[lo: lo + 16])
        assert set(pipe.timings) == {"sign_s", "wait_s", "scatter_s",
                                     "wall_s", "n_batches", "n_items"}
        assert pipe.timings["n_items"] == 64
        # rows over the other half of the universe share no band key with
        # any document: they take the brute-force fallback
        novel = _docs(3, seed=4, lo=D // 2, hi=D)
        svc.query_sparse(np.concatenate([docs[:5], novel]), top_k=3)
        snap = reg.snapshot()
    finally:
        obs_metrics.set_default(old)
    counts = {n: h["count"] for n, h in snap["hists"].items()}
    assert {n: c for n, c in counts.items() if n.startswith("ingest.")} == \
        {"ingest.sign": 4, "ingest.wait": 4, "ingest.scatter": 4,
         "ingest.wall": 5}
    # one batch: the plane's stages once, the fan-out twice (the brute
    # round is a second broadcast and gather), the merge once
    for name, c in {"service.query": 1, "service.sign": 1, "store.query": 1,
                    "query.fold": 1, "query.wall": 1, "query.merge": 1,
                    "query.brute": 1, "query.broadcast": 2,
                    "query.partial": 2, "query.shard0.partial": 2,
                    "query.shard1.partial": 2}.items():
        assert counts[name] == c, name
    assert snap["counters"]["query.brute_rows"] == 3
    assert set(svc.store.last_timings) == {"fold_s", "broadcast_s",
                                           "partial_s", "merge_s"}
    assert all(v >= 0 for v in svc.store.last_timings.values())
    assert svc.store.last_timings["fold_s"] > 0


def test_spilled_match_counts_its_row():
    """A key that overflows its bucket spills; a query row that matches it
    widens its candidates by the spilled ids and is counted once."""
    reg = obs_metrics.Registry()
    old = obs_metrics.set_default(reg)
    try:
        svc = _service(1, bucket_width=2)
        docs = _docs(32, seed=5)
        svc.add_sparse(np.concatenate([docs, docs[:1], docs[:1]]))
        assert svc.store.n_spilled > 0
        ids, _ = svc.query_sparse(docs[:4], top_k=3)
        snap = reg.snapshot()
    finally:
        obs_metrics.set_default(old)
    assert sorted(ids[0]) == [0, 32, 33]       # the spilled copy answers
    assert snap["counters"]["query.spill_rows"] == 1
    assert snap["hists"]["query.spill"]["count"] == 1


def test_kernel_counters_keep_their_names():
    reg = obs_metrics.Registry()
    old = obs_metrics.set_default(reg)
    try:
        svc = _service(1)
        svc.add_sparse(_docs(16, seed=6))
        svc.query_sparse(_docs(2, seed=6), top_k=2)
        snap = reg.snapshot()
    finally:
        obs_metrics.set_default(old)
    names = {n for n in snap["counters"] if n.startswith("kernel.")}
    assert names == {"kernel.sparse.windows", "kernel.fold.jnp",
                     "kernel.query_fused.jnp", "kernel.probe.jnp",
                     "kernel.score.jnp"}
