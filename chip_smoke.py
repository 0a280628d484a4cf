"""Bring-up smoke test: the served sketch plane end to end on one TPU chip.

    python chip_smoke.py             # one chip: the whole served path
    python chip_smoke.py --chips 4   # four chips: mesh signing and the
                                     # S=4 plane, each against one device

One chip, through the entry points a user calls:

1. ``kernels``  — each Pallas leg the served path dispatches to on TPU
   (sparse signing, band-hash fold, collision scorer) against its plain
   reference on a small batch, bit for bit;
2. ``corpus``   — 2^20 documents from ``corpus_with_duplicates`` shingled
   into a 2^16 universe (``batch_shingles``), generated from ``--seed``;
3. ``ingest``   — ``SimilaritySearchService(SearchConfig())`` defaults
   (K=256, 32 bands x 8 rows, b=32, one in-process shard) fed through
   ``svc.pipeline(depth=2)``;
4. ``upload``   — the packed words and LSH records made device-resident;
5. ``query``    — 1,024 indexed documents through ``query_sparse``: top-1
   must be the document itself;
6. ``oracle``   — 256 of them again through the store's host path
   (``query_impl="host"``, numpy probe): answers equal bit for bit;
7. ``stream``   — 512 Poisson arrivals through ``svc.stream()``: no ticket
   rejected, every answer equal to the batch answer.

Each phase prints its wall time split into backend compile and run, the
impl each kernel leg resolved to (the ``kernel.*`` counters of
``repro.obs``), the resident bytes and the device's peak memory.  The last
line of standard output is one JSON object naming the device.  The script
exits non-zero, printing no such line, when JAX's first device is not a
TPU or any phase fails.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from repro.core import cminhash                            # noqa: E402
from repro.core.engine import SketchConfig, SketchEngine   # noqa: E402
from repro.core.lsh import band_hashes_packed              # noqa: E402
from repro.data.shingle import batch_shingles              # noqa: E402
from repro.data.synthetic import corpus_with_duplicates    # noqa: E402
from repro.kernels import dispatch, ops, ref               # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.obs import metrics as obs_metrics               # noqa: E402
from repro.serve.search import SearchConfig, \
    SimilaritySearchService                                # noqa: E402

REQUIRED_PLATFORM = "tpu"
NNZ = 256          # padded shingles per document: 256 tokens -> <= 254
TOP_K = 10



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
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str, report: dict, meter: CompileMeter):
    """Time one phase: wall seconds, backend-compile seconds inside it, and
    the rest as run time."""
    c0, n0, h0 = meter.s, meter.n, meter.cache_hits
    t0 = time.perf_counter()
    row: dict = {}
    yield row
    wall = time.perf_counter() - t0
    comp = meter.s - c0
    row.update(wall_s=round(wall, 3), compile_s=round(comp, 3),
               run_s=round(wall - comp, 3), compiles=meter.n - n0,
               cache_hits=meter.cache_hits - h0)
    report[name] = row
    print(f"phase {name}: " + json.dumps(row), flush=True)


def kernel_impls() -> dict:
    """Which impl each leg resolved to: the ``kernel.*`` call counters."""
    snap = obs_metrics.default().snapshot()["counters"]
    return {n: v for n, v in sorted(snap.items()) if n.startswith("kernel.")}


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def make_corpus(n_docs: int, *, d: int, seed: int,
                chunk: int = 1 << 16) -> np.ndarray:
    """(n_docs, NNZ) padded shingle indices, generated chunk by chunk (each
    chunk its own seeded corpus, so the documents never all sit in memory
    as token lists)."""
    out = np.empty((n_docs, NNZ), np.int32)
    for c, lo in enumerate(range(0, n_docs, chunk)):
        n = min(chunk, n_docs - lo)
        docs, _ = corpus_with_duplicates(n, seed=seed + c)
        out[lo: lo + n] = batch_shingles(docs, d=d, max_nnz=NNZ)
    return out


@contextlib.contextmanager
def host_oracle(svc: SimilaritySearchService):
    """Route the plane's queries through the store's host path: host fold,
    numpy probe, planner scoring — the reference the device path must
    match bit for bit."""
    saved = [(svc.store, "query_impl", svc.store.query_impl)]
    for sh in svc.store.shards:
        saved += [(sh.store, "query_impl", sh.store.query_impl),
                  (sh.store, "probe_impl", sh.store.probe_impl)]
        sh.store.query_impl, sh.store.probe_impl = "host", "numpy"
    svc.store.query_impl = "host"
    try:
        yield
    finally:
        for obj, attr, val in saved:
            setattr(obj, attr, val)


def resident_bytes(svc: SimilaritySearchService) -> dict:
    """Upload the device state and wait for it to land (``device_put``
    returns before the transfer ends); report its logical bytes and what
    the device's layout makes of them (a narrow minor dim pads to whole
    (8, 128) tiles)."""
    out = {"words": 0, "records": 0, "on_device": 0}
    for sh in svc.store.shards:
        for name, x in (("words", sh.store.buffer.device_words()),
                        ("records", sh.store.table.device_records())):
            x.block_until_ready()
            out[name] += int(x.nbytes)
            out["on_device"] += int(x.on_device_size_in_bytes())
    out["total"] = out["words"] + out["records"]
    return out


def check_kernels(report: dict, meter: CompileMeter, *, d: int, k: int,
                  seed: int) -> None:
    """Each TPU-dispatched Pallas leg against its reference, on the chip."""
    with phase("kernels", report, meter) as row:
        idx = jnp.asarray(make_corpus(256, d=d, seed=seed + 10_000))
        eng = SketchEngine(SketchConfig(d=d, k=k, seed=seed))
        got = dispatch.signatures_sparse(idx, eng.pi, k, eng.sigma,
                                         pack_b=32)
        want = dispatch.signatures_sparse(idx, eng.pi, k, eng.sigma,
                                          impl="gather", pack_b=32)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              "sparse signing differs from the gather oracle")
        words = np.asarray(got)
        check(np.array_equal(dispatch.fold_hashes(words, n_bands=32),
                             band_hashes_packed(words, 32)),
              "device band-hash fold differs from the host fold")
        sig = np.asarray(cminhash.cminhash_sparse(idx, eng.pi, k))
        check(np.array_equal(
            np.asarray(ops.collision_counts(sig[:64], sig)),
            np.asarray(ref.collision_count_ref(sig[:64], sig))),
            "collision kernel differs from its reference")
        row["sparse_impl"] = dispatch.select_sparse_impl()


def run_one_chip(meter: CompileMeter, *, n_docs: int, batch: int,
                 n_queries: int, n_oracle: int, n_stream: int,
                 rate_qps: float, seed: int) -> dict:
    """The one-chip smoke: every phase of the module docstring."""
    report: dict = {}
    cfg = SearchConfig(seed=seed)
    check_kernels(report, meter, d=cfg.d, k=cfg.k, seed=seed)

    with phase("corpus", report, meter) as row:
        idx = make_corpus(n_docs, d=cfg.d, seed=seed)
        row.update(docs=n_docs, nnz_mean=float((idx >= 0).sum(1).mean()))

    svc = SimilaritySearchService(cfg)
    with phase("ingest", report, meter) as row:
        with svc.pipeline(depth=2) as pipe:
            for lo in range(0, n_docs, batch):
                pipe.submit(idx[lo: lo + batch])
        check(svc.size == n_docs, f"indexed {svc.size} of {n_docs}")
        row.update(docs=n_docs, docs_per_s=round(n_docs / max(
            pipe.timings["wall_s"], 1e-9), 1),
            **{k: round(v, 3) for k, v in pipe.timings.items()
               if k.endswith("_s")})
        table = svc.store.shards[0].store.table
        row.update(n_slots=table.n_slots, bucket_width=table.bucket_width,
                   n_spilled=svc.store.n_spilled)

    with phase("upload", report, meter) as row:
        report["resident_bytes"] = resident_bytes(svc)
        row.update(report["resident_bytes"])

    rng = np.random.default_rng(seed)
    qids = np.sort(rng.choice(n_docs, n_queries, replace=False))
    qb = min(256, n_queries)
    with phase("query", report, meter) as row:
        parts, batch_s = [], []
        for lo in range(0, n_queries, qb):
            t0 = time.perf_counter()
            parts.append(svc.query_sparse(idx[qids[lo: lo + qb]],
                                          top_k=TOP_K))
            batch_s.append(round(time.perf_counter() - t0, 4))
        ids = np.concatenate([p[0] for p in parts])
        scores = np.concatenate([p[1] for p in parts])
        hit = float((ids[:, 0] == qids).mean())
        row.update(queries=n_queries, top1_self_hit=hit, batch_s=batch_s)
        check(hit == 1.0, f"top-1 self-hit {hit:.4f} < 1")

    with phase("oracle", report, meter) as row:
        with host_oracle(svc):
            o_ids, o_scores = svc.query_sparse(idx[qids[:n_oracle]],
                                               top_k=TOP_K)
        same = (np.array_equal(o_ids, ids[:n_oracle])
                and np.array_equal(o_scores, scores[:n_oracle]))
        row.update(queries=n_oracle, identical=bool(same))
        check(same, "device answers differ from the host oracle")

    with phase("stream", report, meter) as row:
        srng = np.random.default_rng(seed + 1)
        pick = srng.integers(0, n_queries, n_stream)
        gaps = srng.exponential(1.0 / rate_qps, n_stream)
        tickets = []
        with svc.stream(max_batch=256, max_delay_ms=2.0, depth=2,
                        top_k=TOP_K) as stream:
            t0 = time.perf_counter()
            due = t0
            for i, gap in zip(pick, gaps):
                due += gap
                time.sleep(max(0.0, due - time.perf_counter()))
                tickets.append((i, stream.submit_sparse(idx[qids[i]])))
        rejected = wrong = 0
        lat = []
        for i, t in tickets:
            try:
                t_ids, t_scores = t.result(timeout=600)
            except Exception:
                rejected += 1
                continue
            lat.append(t.latency_s)
            if not (np.array_equal(t_ids, ids[i])
                    and np.array_equal(t_scores, scores[i])):
                wrong += 1
        row.update(queries=n_stream, rate_qps=rate_qps, rejected=rejected,
                   wrong=wrong, batches=stream.n_batches,
                   p50_ms=round(float(np.percentile(lat, 50)) * 1e3, 3)
                   if lat else None,
                   p99_ms=round(float(np.percentile(lat, 99)) * 1e3, 3)
                   if lat else None)
        check(rejected == 0, f"{rejected} stream tickets rejected")
        check(wrong == 0, f"{wrong} stream answers differ from batch")

    report["impls"] = kernel_impls()
    report["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
    print("impls: " + json.dumps(report["impls"]), flush=True)
    print(f"resident_bytes: {json.dumps(report['resident_bytes'])}  "
          f"peak_bytes_in_use: {report['peak_bytes_in_use']}", flush=True)
    return report


def run_four_chips(meter: CompileMeter, *, n_docs: int, batch: int,
                   n_queries: int, seed: int) -> dict:
    """Only what spans devices: signing over a 4-device ``data`` mesh, and
    the S=4 in-process plane with shard i's state on device i — each
    against the one-device answers."""
    report: dict = {}
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    cfg = SearchConfig(seed=seed)
    with phase("corpus", report, meter) as row:
        idx = make_corpus(n_docs, d=cfg.d, seed=seed)
        row.update(docs=n_docs)

    with phase("mesh_sign", report, meter) as row:
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        scfg = SketchConfig(d=cfg.d, k=cfg.k, seed=seed)
        one, four = SketchEngine(scfg), SketchEngine(scfg, mesh=mesh)
        same = True
        for lo in range(0, n_docs, batch):
            x = jnp.asarray(idx[lo: lo + batch])
            same &= np.array_equal(np.asarray(one.sign(x, pack_b=32)),
                                   np.asarray(four.sign(x, pack_b=32)))
        row.update(docs=n_docs, identical=bool(same))
        check(same, "mesh signing differs from one device")

    answers = {}
    for s in (1, 4):
        with phase(f"plane_s{s}", report, meter) as row:
            svc = SimilaritySearchService(SearchConfig(seed=seed,
                                                       n_shards=s))
            with svc.pipeline(depth=2) as pipe:
                for lo in range(0, n_docs, batch):
                    pipe.submit(idx[lo: lo + batch])
            placed = [sorted(d.id for d in sh.store.buffer.device_words()
                             .devices() | sh.store.table.device_records()
                             .devices()) for sh in svc.store.shards]
            t0 = time.perf_counter()
            answers[s] = svc.query_sparse(idx[:n_queries], top_k=TOP_K)
            row.update(shard_devices=placed,
                       query_s=round(time.perf_counter() - t0, 4),
                       top1_self_hit=float(
                           (answers[s][0][:, 0] == np.arange(n_queries))
                           .mean()))
            if s == 4:
                check(placed == [[i] for i in range(4)],
                      f"shard state not one shard per device: {placed}")
    same = all(np.array_equal(a, b) for a, b in zip(answers[1], answers[4]))
    report["plane_s4"]["identical_to_s1"] = bool(same)
    print(f"plane S=4 == S=1: {same}", flush=True)
    check(same, "S=4 answers differ from S=1")
    report["impls"] = kernel_impls()
    print("impls: " + json.dumps(report["impls"]), flush=True)
    return report


def require_platform() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != REQUIRED_PLATFORM:
        raise SystemExit(f"chip_smoke: needs a {REQUIRED_PLATFORM} device; "
                         f"JAX found platform {dev.platform!r}")
    return dev


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_platform()
    cache = setup_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"  compile cache: {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        with CompileMeter() as meter:
            if args.chips == 1:
                run_one_chip(meter, n_docs=1 << 20, batch=8192,
                             n_queries=1024, n_oracle=256, n_stream=512,
                             rate_qps=2000.0, seed=args.seed)
            else:
                run_four_chips(meter, n_docs=1 << 18, batch=8192,
                               n_queries=1024, seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={meter.s:.3f} compiles={meter.n} "
          f"cache_hits={meter.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
