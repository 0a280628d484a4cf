"""One run of one cell: set-up, the measured window, the check.

Two kinds of traffic, named by the mix file's ``kind``:

* ``query_stream`` — an index of ``index_docs`` seeded documents is built
  through ``svc.pipeline`` and made device-resident, every power-of-two
  batch shape the stream front can form is warmed, and then queries arrive
  open-loop on the mix's schedule through ``svc.stream(...)``
  (``StreamingQueryService.submit_sparse``).  Each query is timed from its
  due time to its resolved ticket.  Every answer of the window is checked.
* ``ingest`` — fresh seeded documents go closed-loop through
  ``svc.pipeline(depth)`` in fixed batches into a table pre-sized by the
  mix; the window counts documents signed, packed and inserted.  The index
  it built is checked through the answers it gives afterwards.

The check runs once the window has closed, the peak memory has been read
and the program's state is freed: ``reference.Reference`` answers the same
queries from the same documents, and the run is correct when no answer
differs, none is missing, and (ingest) no document is lost.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import time

import numpy as np

from . import gen, manifest, trace as btrace
from .reference import Reference, count_wrong

LATE_S = 60.0      # an answer may come this long after the window closes


class CompileMeter:
    """Backend-compile seconds and count, and persistent-cache hits, from
    JAX's monitoring events while the meter is entered."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.s, self.n, self.cache_hits = 0.0, 0, 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE_EVENT:
            self.s += duration
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def resident_bytes(svc) -> dict:
    """Upload the device state and wait for it to land (``device_put``
    returns before the transfer ends); its logical bytes and what the
    device's layout makes of them."""
    out = {"words": 0, "records": 0, "on_device": 0}
    for sh in svc.store.shards:
        for name, x in (("words", sh.store.buffer.device_words()),
                        ("records", sh.store.table.device_records())):
            x.block_until_ready()
            out[name] += int(x.nbytes)
            out["on_device"] += int(x.on_device_size_in_bytes())
    return out


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def build_service(cfg: dict, program_seed: int, n_slots: int, b: int):
    from repro.serve.search import SearchConfig, SimilaritySearchService
    return SimilaritySearchService(SearchConfig(
        d=cfg["d"], k=cfg["k"], n_bands=cfg["n_bands"],
        rows_per_band=cfg["rows_per_band"], b=b, seed=program_seed,
        n_slots=n_slots, n_shards=cfg["n_shards"]))


def slots_for(n_docs: int) -> int:
    """The slot count the table's own growth reaches for ``n_docs``
    (load <= 0.7, power of two), set up front so the build never rebuilds."""
    return max(2048, 1 << int(np.ceil(np.log2(n_docs / 0.7))))


class Probes:
    """Trace-mode instrumentation from the benchmark's side: each call into
    a layer is wrapped in a ``bench.*`` ``TraceAnnotation`` on the service's
    own instances, and the rows that reach the device legs are counted."""

    def __init__(self):
        self.rows_signed = 0
        self.rows_queried = 0

    def wrap(self, obj, attr: str, label: str, count: str | None = None):
        import jax
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            if count is not None:
                setattr(self, count, getattr(self, count) + len(a[0]))
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def install(self, svc) -> None:
        self.wrap(svc, "_sign", "bench.sign", "rows_signed")
        self.wrap(svc, "_query", "bench.query", "rows_queried")
        self.wrap(svc, "_scatter", "bench.insert")
        self.wrap(svc.store, "_fold_packed", "bench.fold")
        for sh in svc.store.shards:
            self.wrap(sh.store, "partial_topk_packed_hashed", "bench.partial")


@dataclasses.dataclass
class Run:
    """What a run hands to the metric readers and to the result line."""
    kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    compared: dict = dataclasses.field(default_factory=dict)
    lat_ms: np.ndarray | None = None
    docs_in_window: int = 0
    delta: dict = dataclasses.field(default_factory=dict)
    n_batches: int = 0
    trace: dict | None = None
    rows_signed: int = 0
    rows_queried: int = 0
    bucket_width: int = 0
    n_bands: int = 0
    peaks: dict | None = None
    memory_peak_bytes: int | None = None
    info: dict = dataclasses.field(default_factory=dict)

    def ctx(self) -> dict:
        return dataclasses.asdict(self)


def _wait_until(t: float) -> None:
    """Sleep to ``t`` (never spin: a spinning generator would hold the
    interpreter lock the stream front's thread needs)."""
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def _answer(ticket, deadline: float):
    """A ticket's (ids, scores), or None when it failed or is still out
    at ``deadline``: such an answer is missing."""
    try:
        return ticket.result(timeout=max(deadline - time.perf_counter(), 0))
    except Exception:          # a failed or late query: counted, not raised
        return None


def steady_heap() -> None:
    """Collect once and move everything set-up made into the collector's
    permanent generation, so no full collection in the window walks the
    index, the corpus or JAX's caches."""
    gc.collect()
    gc.freeze()


def _snapshot():
    from repro.obs import metrics as obs_metrics
    return obs_metrics.default().snapshot()


def _delta(before):
    from repro.obs import metrics as obs_metrics
    return obs_metrics.snapshot_delta(before, _snapshot())


def _build_index(svc, idx: np.ndarray, batch: int) -> None:
    with svc.pipeline(depth=2) as pipe:
        for lo in range(0, len(idx), batch):
            pipe.submit(idx[lo: lo + batch])
    if svc.size != len(idx):
        raise RuntimeError(f"indexed {svc.size} of {len(idx)} documents")


def _warm(svc, rows: np.ndarray, spilled: np.ndarray, max_batch: int,
          top_k: int) -> None:
    """Compile every batch shape the stream front can form: each power of
    two up to ``max_batch``, once with plain rows and, when the table holds
    spilled entries, once with a spilled document's row in front, since a
    query that matches a spilled key widens its batch's candidate rows."""
    size = 1
    while size <= max_batch:
        svc.query_sparse(rows[:size], top_k=top_k)
        if len(spilled):
            svc.query_sparse(np.concatenate([spilled, rows[:size - 1]]),
                             top_k=top_k)
        size *= 2


def setup_query_index(cfg: dict, tr: dict, ks: dict, n_queries: int,
                      b: int, log=print):
    """Generate the index documents and ``n_queries`` queries (plus the
    warm-up rows after them), build and upload the index, and warm every
    batch shape.  Returns (service, document rows, query rows, info)."""
    n_docs, top_k = cfg["index_docs"], cfg["top_k"]
    rng = np.random.default_rng(ks["numpy"])
    t = time.perf_counter()
    corpus = gen.Corpus(cfg["corpus"], cfg["d"], ks["corpus"], n_docs)
    src = rng.integers(0, n_docs, n_queries + tr["max_batch"])
    qrows = corpus.queries(ks["queries"], src, tr["query_edit_tokens"])
    idx = corpus.idx
    del corpus
    log(f"generate: {time.perf_counter() - t:.3f} s for {n_docs} documents "
        f"and {len(qrows)} queries")
    t = time.perf_counter()
    svc = build_service(cfg, ks["program"], slots_for(n_docs), b)
    _build_index(svc, idx, cfg["build_batch"])
    log(f"build: {time.perf_counter() - t:.3f} s, n_spilled "
        f"{svc.store.n_spilled}")
    t = time.perf_counter()
    info = {"resident": resident_bytes(svc)}
    log(f"upload: {time.perf_counter() - t:.3f} s {info['resident']}")
    t = time.perf_counter()
    table = svc.store.shards[0].store.table
    _warm(svc, qrows[n_queries:], idx[table.spilled_ids()[:1]],
          tr["max_batch"], top_k)
    log(f"warm: {time.perf_counter() - t:.3f} s")
    return svc, idx, qrows, info


def open_loop(stream, rows: np.ndarray, due: np.ndarray, seconds: float,
              top_k: int) -> dict:
    """Submit ``rows[i]`` at ``due[i]`` seconds from now and collect every
    answer (``LATE_S`` past the close at most).  Answers are harvested in
    arrival order as they resolve, so only the tickets in flight stay
    alive, as in a client that hands each answer on.  Returns the due times
    as ``perf_counter`` readings, the generator's lateness, each answer's
    resolve time (NaN: failed or never came), ids and scores."""
    n = len(due)
    got = {"late": np.zeros(n), "done": np.full(n, np.nan),
           "ids": np.full((n, top_k), -1, np.int64),
           "scores": np.zeros((n, top_k), np.float32)}
    pending: collections.deque = collections.deque()

    def harvest(i, ticket, deadline):
        ans = _answer(ticket, deadline)
        if ans is not None:
            got["ids"][i, :len(ans[0])] = ans[0]
            got["scores"][i, :len(ans[1])] = ans[1]
            got["done"][i] = ticket.t_done

    t0 = time.perf_counter()
    due_abs = got["due"] = t0 + due
    submit = stream.submit_sparse
    for i in range(n):
        _wait_until(due_abs[i])
        got["late"][i] = time.perf_counter() - due_abs[i]
        pending.append((i, submit(rows[i])))
        while pending[0][1].done:
            harvest(*pending.popleft(), 0.0)
            if not pending:
                break
    while pending:
        harvest(*pending.popleft(), t0 + seconds + LATE_S)
    return got


def run_query_stream(cfg: dict, tr: dict, *, seed: int, seconds: float,
                     t_start: float, traced: bool, b: int, hook=None,
                     log=print) -> Run:
    import jax
    run = Run(kind="query")
    ks = gen.keys(seed)
    top_k = cfg["top_k"]
    due = gen.arrival_offsets(tr["phases"], seconds, ks["numpy"] + 1)
    n_q = len(due)
    svc, idx, qrows, run.info = setup_query_index(cfg, tr, ks, n_q, b, log)
    table = svc.store.shards[0].store.table
    run.bucket_width, run.n_bands = table.bucket_width, cfg["n_bands"]
    probes = Probes()
    if traced:
        probes.install(svc)
    if hook is not None:
        hook(svc)
    stream = svc.stream(max_batch=tr["max_batch"],
                        max_delay_ms=tr["max_delay_ms"], depth=tr["depth"],
                        top_k=top_k)
    if traced:
        probes.wrap(stream, "_drain_one", "bench.drain")
    before = _snapshot()
    b0 = stream.n_batches
    steady_heap()
    with CompileMeter() as meter, btrace.record(traced) as rec:
        run.setup_s = time.perf_counter() - t_start
        ann = (jax.profiler.TraceAnnotation("bench.window") if traced
               else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            got = open_loop(stream, qrows, due, seconds, top_k)
            run.window_s = time.perf_counter() - t0
        compiles = meter.n
    stream.close()
    gc.unfreeze()
    run.delta = _delta(before)
    run.n_batches = stream.n_batches - b0
    run.rows_signed, run.rows_queried = probes.rows_signed, probes.rows_queried
    run.memory_peak_bytes = peak_bytes(jax.devices())

    ids, scores, late = got["ids"], got["scores"], got["late"]
    ok = ~np.isnan(got["done"])
    run.lat_ms = (got["done"] - got["due"])[ok] * 1e3
    run.attempted = n_q
    missing = int(n_q - ok.sum())
    run.info.update(queries=n_q, batches=run.n_batches,
                    late_p50_ms=float(np.percentile(late, 50) * 1e3),
                    late_max_ms=float(late.max() * 1e3),
                    late_max_at_s=float(due[np.argmax(late)]),
                    compiles_in_window=compiles)
    if traced:
        run.trace = _reduce(rec)
    del svc, stream, table
    gc.collect()

    t = time.perf_counter()
    ref = Reference(d=cfg["d"], k=cfg["k"], n_bands=cfg["n_bands"],
                    rows_per_band=cfg["rows_per_band"], seed=ks["program"])
    doc_sigs = ref.signatures(idx).block_until_ready()
    log(f"reference: signed {len(idx)} documents in "
        f"{time.perf_counter() - t:.3f} s")
    ref_ids, ref_scores = ref.topk(doc_sigs, ref.signatures(qrows[:n_q]),
                                   top_k)
    wrong = count_wrong(ids[ok], scores[ok], ref_ids[ok], ref_scores[ok])
    run.info["no_candidate_queries"] = len(ref.no_candidate)
    log(f"reference: {time.perf_counter() - t:.3f} s in all")
    run.failed = missing + wrong
    run.compared = {"wrong_answers": {"value": wrong, "limit": 0},
                    "missing_answers": {"value": missing, "limit": 0}}
    return run


def run_ingest(cfg: dict, tr: dict, *, seed: int, seconds: float,
               t_start: float, traced: bool, b: int, hook=None,
               log=print) -> Run:
    import jax
    import jax.numpy as jnp
    run = Run(kind="ingest")
    ks = gen.keys(seed)
    batch, top_k = tr["batch"], cfg["top_k"]
    t = time.perf_counter()
    corpus = gen.Corpus(cfg["corpus"], cfg["d"], ks["corpus"],
                        tr["fresh_docs"], keep_tokens=True)
    log(f"generate: {time.perf_counter() - t:.3f} s for {tr['fresh_docs']} "
        f"documents")
    idx = corpus.idx
    svc = build_service(cfg, ks["program"], tr["n_slots"], b)
    np.asarray(svc.engine.sign(jnp.asarray(idx[:batch]), layout="sparse",
                               pack_b=b))
    run.n_bands = cfg["n_bands"]
    probes = Probes()
    if traced:
        probes.install(svc)
    if hook is not None:
        hook(svc)
    pipe = svc.pipeline(depth=tr["depth"])
    if traced:
        probes.wrap(pipe, "_drain_one", "bench.drain")
    before = _snapshot()
    n_sub = 0
    steady_heap()
    with CompileMeter() as meter, btrace.record(traced) as rec:
        run.setup_s = time.perf_counter() - t_start
        ann = (jax.profiler.TraceAnnotation("bench.window") if traced
               else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            end = t0 + seconds
            while time.perf_counter() < end and n_sub < len(idx):
                pipe.submit(idx[n_sub: n_sub + batch])
                n_sub = min(n_sub + batch, len(idx))
            run.window_s = time.perf_counter() - t0
            run.docs_in_window = pipe.n_items
        compiles = meter.n
    gc.unfreeze()
    run.delta = _delta(before)
    run.rows_signed = probes.rows_signed
    pipe.flush()
    run.memory_peak_bytes = peak_bytes(jax.devices())
    if traced:
        run.trace = _reduce(rec)
    run.attempted = n_sub
    lost = n_sub - svc.size
    run.info.update(docs_submitted=n_sub, docs_in_window=run.docs_in_window,
                    compiles_in_window=compiles,
                    n_spilled=svc.store.n_spilled)
    if n_sub >= len(idx):
        log(f"ingest: all {len(idx)} fresh documents used before the "
            f"window closed")

    # the index the window built, through the answers it gives afterwards
    rng = np.random.default_rng(ks["numpy"])
    src = rng.integers(0, max(n_sub, 1), tr["check_queries"])
    qrows = corpus.queries(ks["queries"], src, tr["query_edit_tokens"])
    ids = np.full((len(qrows), top_k), -1, np.int64)
    scores = np.zeros((len(qrows), top_k), np.float32)
    answered = np.zeros(len(qrows), bool)
    for lo in range(0, len(qrows), tr["check_batch"]):
        sl = slice(lo, lo + tr["check_batch"])
        try:
            ids[sl], scores[sl] = svc.query_sparse(qrows[sl], top_k=top_k)
        except Exception as e:       # an index that cannot answer: counted
            log(f"check query batch at {lo} failed: {e!r}")
            continue
        answered[sl] = True
    missing = int((~answered).sum())
    del svc, pipe
    gc.collect()

    t = time.perf_counter()
    ref = Reference(d=cfg["d"], k=cfg["k"], n_bands=cfg["n_bands"],
                    rows_per_band=cfg["rows_per_band"], seed=ks["program"])
    doc_sigs = ref.signatures(idx[:n_sub]).block_until_ready()
    log(f"reference: signed {n_sub} documents in "
        f"{time.perf_counter() - t:.3f} s")
    ref_ids, ref_scores = ref.topk(doc_sigs, ref.signatures(qrows), top_k)
    wrong = count_wrong(ids[answered], scores[answered], ref_ids[answered],
                        ref_scores[answered])
    run.info["no_candidate_queries"] = len(ref.no_candidate)
    log(f"reference: {time.perf_counter() - t:.3f} s in all")
    run.failed = lost + wrong + missing
    run.compared = {"wrong_answers": {"value": wrong, "limit": 0},
                    "missing_answers": {"value": missing, "limit": 0},
                    "lost_documents": {"value": lost, "limit": 0}}
    return run


def _reduce(rec: dict) -> dict | None:
    try:
        if rec.get("path") is None:
            return None
        return btrace.reduce(btrace.load(rec["path"]))
    finally:
        btrace.cleanup(rec)


KINDS = {"query_stream": run_query_stream, "ingest": run_ingest}


def run_cell(cell: manifest.Cell, *, seed: int, seconds: float, traced: bool,
             t_start: float, b: int | None = None, hook=None,
             log=print) -> dict:
    """One run: the result line's object (``compared`` last)."""
    import jax
    from . import peaks as bpeaks
    devices = jax.devices()
    run = KINDS[cell.traffic["kind"]](
        cell.config, cell.traffic, seed=seed, seconds=seconds,
        t_start=t_start, traced=traced,
        b=cell.config["b"] if b is None else b, hook=hook, log=log)
    dev = devices[0]
    if dev.platform == "tpu":
        run.peaks = bpeaks.peaks(dev.device_kind)
    entries = cell.per_layer if traced else cell.end_to_end
    metrics = manifest.read_metrics(entries, run.ctx())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.failed == 0 and all(
               c["value"] <= c["limit"] for c in run.compared.values()),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["info"] = run.info
    out["compared"] = run.compared
    return out
