"""Serving drivers: LM generation and signature-based similarity search.

    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch llama3_2_1b
    PYTHONPATH=src python -m repro.launch.serve --mode search --docs 400

Observability (search mode): ``--metrics-dump PATH`` appends one JSONL
snapshot of the process metrics registry (+ drained trace spans) every
``--metrics-interval`` seconds while the driver runs, plus a final line at
shutdown — validate with ``python -m repro.obs.dump --check PATH``.
``--trace-sample-rate`` sets the root-span sampling probability (1.0 =
trace every query batch; sampled traces ride the wire to tcp shard workers
and come back stitched).
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import build
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.dump import MetricsDumper
from repro.serve.decode import generate


def serve_lm(args) -> None:
    cfg = reduced(get_config(args.arch))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": np.asarray(
        rng.integers(0, cfg.vocab_size_real, (args.batch, args.prompt_len)),
        np.int32)}
    if cfg.frontend == "frames":
        batch["frames"] = rng.normal(
            size=(args.batch, args.prompt_len, cfg.d_model)).astype(np.float32)
    if cfg.frontend == "patches":
        batch["patches"] = rng.normal(
            size=(args.batch, args.prompt_len // 8, cfg.d_model)
        ).astype(np.float32)
    t0 = time.perf_counter()
    toks = generate(bundle, params, batch, max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
    dt = time.perf_counter() - t0
    n = args.batch * args.new_tokens
    print(f"[serve] {cfg.name}: generated {n} tokens in {dt:.2f}s "
          f"({n / dt:.0f} tok/s, batch={args.batch})")
    print(f"[serve] sample: {toks[0][:16].tolist()}")


def serve_search(args) -> None:
    from repro.data.shingle import batch_shingles
    from repro.data.synthetic import corpus_with_duplicates
    from repro.serve.search import SearchConfig, SimilaritySearchService
    obs_trace.default().sample_rate = args.trace_sample_rate
    docs, _ = corpus_with_duplicates(args.docs, vocab=30_000, doc_len=256,
                                     dup_fraction=0.4, seed=0)
    idx = batch_shingles(docs, n=3, d=1 << 14)
    dumper = (MetricsDumper(args.metrics_dump,
                            interval_s=args.metrics_interval)
              if args.metrics_dump else contextlib.nullcontext())
    # tcp: one shard worker process per shard on localhost, reaped by
    # close() — same answers as inproc, bit-for-bit
    with dumper, SimilaritySearchService(SearchConfig(
            d=1 << 14, k=256, n_bands=64, rows_per_band=4,
            n_shards=args.shards, partition=args.partition,
            probe_impl=args.probe, query_impl=args.query_impl,
            transport=args.transport,
            query_timeout_s=args.query_timeout,
            hedge=args.hedge,
            hedge_delay_ms=args.hedge_delay_ms,
            n_replicas=args.replicas,
            journal_dir=args.journal_dir,
            supervisor=args.supervisor)) as svc:
        # pipelined fused ingest: batch N+1 signs while batch N scatters
        # (--pipeline-depth 1 = serial; answers identical at any depth)
        bs = max(1, min(args.ingest_batch, len(idx)))
        t0 = time.perf_counter()
        with svc.pipeline(depth=args.pipeline_depth) as pipe:
            for lo in range(0, len(idx), bs):
                pipe.submit(idx[lo: lo + bs])
        t_ingest = time.perf_counter() - t0
        tm = pipe.timings
        print(f"[serve] ingest {svc.size} docs in {t_ingest * 1e3:.1f} ms "
              f"(depth={args.pipeline_depth}, "
              f"{svc.size / t_ingest:.0f} docs/s; sign={tm['sign_s'] * 1e3:.0f}ms "
              f"wait={tm['wait_s'] * 1e3:.0f}ms "
              f"scatter={tm['scatter_s'] * 1e3:.0f}ms)")
        t0 = time.perf_counter()
        ids, scores = svc.query_sparse(idx[: args.batch], top_k=5)
        dt = time.perf_counter() - t0
        sizes = svc.store.shard_sizes().tolist()
        print(f"[serve] search over {svc.size} docs "
              f"({args.shards} shard(s) {sizes}, probe={args.probe}, "
              f"query={args.query_impl}, transport={args.transport}): "
              f"{args.batch} queries in {dt * 1e3:.1f} ms; top-1 self-hit "
              f"{(ids[:, 0] == np.arange(args.batch)).mean() * 100:.0f}%")
        if args.stream:
            # open-loop streaming demo: Poisson arrivals at --stream-qps
            # through the admission queue; the percentiles are client-side
            # end-to-end (admission wait + batch wall), the honest number
            # an outside caller would see
            rng = np.random.default_rng(1)
            n_q = args.stream_queries
            qrows = idx[rng.integers(0, len(idx), n_q)]
            gaps = rng.exponential(1.0 / args.stream_qps, n_q)
            with svc.stream(max_batch=args.max_batch,
                            max_delay_ms=args.max_delay_ms,
                            depth=args.stream_depth) as stream:
                t0 = time.perf_counter()
                tickets = []
                for i in range(n_q):
                    target = t0 + gaps[: i + 1].sum()
                    while time.perf_counter() < target:
                        time.sleep(min(target - time.perf_counter(), 1e-3))
                    tickets.append(stream.submit_sparse(qrows[i], top_k=5))
                for t in tickets:
                    t.result(timeout=svc.cfg.query_timeout_s + 30)
                wall = time.perf_counter() - t0
            lat = np.sort([t.latency_s for t in tickets])
            print(f"[serve] stream: {n_q} queries at {args.stream_qps:.0f} "
                  f"qps offered -> {n_q / wall:.0f} qps served "
                  f"({stream.n_batches} batches, depth={args.stream_depth}, "
                  f"hedge={'on' if args.hedge else 'off'}); e2e p50 "
                  f"{lat[int(0.50 * (n_q - 1))] * 1e3:.2f} ms, p99 "
                  f"{lat[int(0.99 * (n_q - 1))] * 1e3:.2f} ms")
        # one merged plane snapshot (coordinator + tcp workers): the
        # per-shard partial-latency split is the skew evidence
        snap = svc.store.obs_snapshot()
        shard_p50 = [
            obs_metrics.hist_quantile(
                snap["hists"].get(f"query.shard{i}.partial",
                                  {"count": 0, "buckets": {}}), 0.5)
            for i in range(args.shards)]
        print(f"[serve] obs: {len(snap['counters'])} counters, "
              f"{len(snap['hists'])} hists; shard partial p50(ms) "
              f"{[None if p is None else round(p * 1e3, 2) for p in shard_p50]}")
        # stitched-trace summary (skipped when a dumper already drained the
        # ring — the spans live in the dump file then)
        tid = obs_trace.default().last_trace_id()
        spans = obs_trace.default().for_trace(tid) if tid is not None else []
        if spans:
            legs = sorted({(s["proc"], s["name"]) for s in spans})
            print(f"[serve] trace {tid:x}: {len(spans)} spans across "
                  f"{len({p for p, _ in legs})} proc(s): "
                  f"{', '.join(f'{p}/{n}' for p, n in legs)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "search"], default="lm")
    ap.add_argument("--arch", default="llama3_2_1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--docs", type=int, default=400)
    ap.add_argument("--shards", type=int, default=1,
                    help="index partitions (search mode)")
    ap.add_argument("--partition", choices=["round_robin", "hash"],
                    default="round_robin")
    ap.add_argument("--probe", choices=["auto", "numpy", "jnp", "pallas"],
                    default="auto", help="LSH bucket-probe backend")
    ap.add_argument("--query-impl",
                    choices=["auto", "jnp", "pallas", "host"],
                    default="auto",
                    help="fused device query pipeline backend (host = "
                         "legacy fold + planner walk, the reference oracle)")
    ap.add_argument("--transport", choices=["inproc", "tcp"],
                    default="inproc",
                    help="shard backend: in-process loop or spawned tcp "
                         "shard workers (search mode)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="ingest batches signed-but-unscattered in flight "
                         "(1 = serial sign->scatter; search mode)")
    ap.add_argument("--ingest-batch", type=int, default=128,
                    help="documents per ingest pipeline batch (search mode)")
    ap.add_argument("--query-timeout", type=float, default=30.0,
                    dest="query_timeout",
                    help="query fan-out deadline in seconds (tcp transport; "
                         "TransportTimeout errors name this knob)")
    ap.add_argument("--hedge", action="store_true",
                    help="hedge slow shard reads on a second connection "
                         "(tcp transport; never changes results)")
    ap.add_argument("--hedge-delay-ms", type=float, default=None,
                    help="fixed hedge delay in ms (default: derived from "
                         "observed per-shard reply latencies; 0 hedges "
                         "immediately)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica workers per shard (tcp transport; 1 = "
                         "the classic unreplicated plane, bit-identical)")
    ap.add_argument("--journal-dir", default=None,
                    help="directory for the write-ahead ingest journal "
                         "(tcp transport; required for replica resync)")
    ap.add_argument("--supervisor", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="self-heal dead replicas: respawn, replay the "
                         "journal, digest-verify, rejoin (--replicas > 1)")
    ap.add_argument("--stream", action="store_true",
                    help="run the open-loop streaming demo after ingest "
                         "(search mode)")
    ap.add_argument("--stream-qps", type=float, default=500.0,
                    help="offered Poisson arrival rate for --stream")
    ap.add_argument("--stream-queries", type=int, default=512,
                    help="queries to stream for --stream")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="admission queue flush size (--stream)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="admission queue flush deadline in ms (--stream)")
    ap.add_argument("--stream-depth", type=int, default=2,
                    help="streaming pipeline depth: batches in flight "
                         "(1 = serial; --stream)")
    ap.add_argument("--metrics-dump", metavar="PATH", default=None,
                    help="append periodic JSONL registry snapshots + trace "
                         "spans here while serving (search mode); validate "
                         "with `python -m repro.obs.dump --check PATH`")
    ap.add_argument("--metrics-interval", type=float, default=1.0,
                    help="seconds between --metrics-dump lines")
    ap.add_argument("--trace-sample-rate", type=float, default=1.0,
                    help="probability a query batch opens a (cross-process) "
                         "trace; 0 disables tracing (search mode)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.mode == "lm":
        serve_lm(args)
    else:
        serve_search(args)


if __name__ == "__main__":
    main()
