"""Batched similarity-search service over C-MinHash signatures.

Index + query path is owned by the sharded SketchStore plane: signatures
live in b-bit packed device buffers partitioned across ``n_shards`` shards,
LSH bucketing is open-addressing array state per shard (no per-item Python
dicts), and a query batch is answered with one band-hash fold broadcast to
every shard, per-shard candidate gather + collision-kernel scoring, and a
mergeable top-k reduction (``distributed.collectives.merge_topk``).  At the
default ``n_shards=1`` the pipeline degenerates to the single-store path and
results are bit-identical to it; raising ``n_shards`` changes *where* items
live, never *what* a query answers.  At the default ``b=32`` the stored
codes are the exact signatures, so results match the unpacked reference path
bit-for-bit; ``b<32`` trades a small upward score bias (Li & Koenig, 2011)
for 32/b smaller index memory.  ``probe_impl`` picks the bucket-probe
backend ("auto": numpy host loop on CPU, device Pallas kernel on TPU).

``transport`` picks where the shards live: ``"inproc"`` (default) runs them
in this process; ``"tcp"`` spawns one shard worker process per shard on
localhost and talks the framed wire protocol (``repro.transport``) — same
answers bit-for-bit, but the index outgrows one process.  tcp services own
their workers: call ``close()`` (or use the service as a context manager)
to shut them down.

Ingest runs the fused sign->pack fast path end-to-end whenever the banding
is word-aligned (``rows_per_band % (32/b) == 0``; always true at the
default b = 32): signatures leave the kernel as b-bit packed words
(``SketchEngine.sign_packed``) and are indexed from the words directly
(``add_packed``/``query_packed``) — no (B, K) int32 batch ever forms on the
host, and at b = 32 answers are bit-identical to the raw-signature path.
``IngestPipeline`` adds double-buffering on top: batch N+1's signing is
dispatched (JAX async) while batch N scatters into the shards, so device
and host work overlap instead of strictly alternating.
"""

from __future__ import annotations

import collections
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.engine import SketchConfig, SketchEngine
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.store import ShardedSketchStore, StoreConfig

TRANSPORTS = ("inproc", "tcp")


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    d: int = 1 << 16
    k: int = 256
    n_bands: int = 32
    rows_per_band: int = 8
    seed: int = 0
    b: int = 32                 # stored bits per hash (32 = exact scoring)
    n_slots: int = 2048         # initial LSH table slots per band (per shard)
    bucket_width: int = 8       # initial postings per bucket
    n_shards: int = 1           # index partitions (1 = single-store path)
    partition: str = "round_robin"   # or "hash" (see store/sharded.py)
    probe_impl: str = "auto"    # LSH probe backend: numpy | jnp | pallas
    query_impl: str = "auto"    # fused query backend: jnp | pallas | host
    transport: str = "inproc"   # shard backend: inproc | tcp (worker procs)
    query_timeout_s: float = 30.0    # fan-out deadline (tcp transport)
    hedge: bool = False         # hedged shard reads (tcp transport)
    hedge_delay_ms: float | None = None  # fixed hedge delay; None = derived
    # replication (tcp transport; see repro.replica): R workers per shard,
    # a write-ahead ingest journal, and a self-healing supervisor.  At the
    # default n_replicas=1 with no journal the classic unreplicated plane
    # is built — bit-identical to before these knobs existed.
    n_replicas: int = 1         # replica lanes per shard
    journal_dir: str | None = None   # write-ahead ingest journal directory
    supervisor: bool = True     # self-heal dead replicas (n_replicas > 1)


class SimilaritySearchService:
    def __init__(self, cfg: SearchConfig, mesh=None, *,
                 store=None, workers=None):
        """``store``/``workers`` inject a pre-built shard plane (benchmarks
        and tests spawn planes with injected-slow workers); by default the
        service builds its own per ``cfg.transport``."""
        if cfg.n_bands * cfg.rows_per_band != cfg.k:
            raise ValueError("n_bands * rows_per_band must equal k")
        if cfg.transport not in TRANSPORTS:
            raise ValueError(f"transport must be one of {TRANSPORTS} "
                             f"(got {cfg.transport!r})")
        self.cfg = cfg
        self.engine = SketchEngine(SketchConfig(d=cfg.d, k=cfg.k,
                                                seed=cfg.seed), mesh=mesh)
        store_cfg = StoreConfig(k=cfg.k, n_bands=cfg.n_bands,
                                rows_per_band=cfg.rows_per_band, b=cfg.b,
                                n_slots=cfg.n_slots,
                                bucket_width=cfg.bucket_width)
        self._workers: list = list(workers) if workers else []
        self._supervisor = None
        if store is not None:
            self.store = store
        elif cfg.transport == "tcp" and (cfg.n_replicas > 1
                                         or cfg.journal_dir is not None):
            self._build_replicated(store_cfg)
        elif cfg.transport == "tcp":
            from repro.transport import (HedgePolicy, connect_sharded,
                                         spawn_workers)
            self._workers = spawn_workers(store_cfg, cfg.n_shards,
                                          probe_impl=cfg.probe_impl,
                                          query_impl=cfg.query_impl)
            hedge = None
            if cfg.hedge:
                # hedge_delay_ms=0.0 is a valid fixed delay (hedge at
                # once), so the None check must be explicit
                hedge = HedgePolicy() if cfg.hedge_delay_ms is None \
                    else HedgePolicy(delay_s=cfg.hedge_delay_ms / 1e3)
            try:
                self.store = connect_sharded(
                    [h.address for h in self._workers], store_cfg,
                    partition=cfg.partition, query_impl=cfg.query_impl,
                    timeout=cfg.query_timeout_s, hedge=hedge)
            except BaseException:
                for h in self._workers:    # no orphan worker processes
                    h.terminate()
                raise
        else:
            self.store = ShardedSketchStore(
                store_cfg, n_shards=cfg.n_shards, partition=cfg.partition,
                probe_impl=cfg.probe_impl, query_impl=cfg.query_impl)
        reg, tracer = obs_metrics.default(), obs_trace.default()
        self._t_query = obs_trace.Timer("service.query", reg, tracer)
        self._t_sign = obs_trace.Timer("service.sign", reg, tracer)

    def _build_replicated(self, store_cfg: StoreConfig) -> None:
        """The replicated tcp plane: an S x R worker grid, a write-ahead
        ingest journal, and (by default) the self-healing supervisor.
        Hedging is always armed here — the failure-triggered hedge IS the
        in-round read failover to a sibling replica — with
        ``hedge_delay_ms`` still honored as a fixed-delay override."""
        import os

        from repro.replica import (IngestJournal, Supervisor,
                                   connect_replicated, spawn_replicated)
        from repro.transport import HedgePolicy
        cfg = self.cfg
        journal = None
        if cfg.journal_dir is not None:
            journal = IngestJournal(
                os.path.join(cfg.journal_dir, "ingest.journal"))
        grid = spawn_replicated(store_cfg, cfg.n_shards,
                                max(cfg.n_replicas, 1),
                                probe_impl=cfg.probe_impl,
                                query_impl=cfg.query_impl)
        self._workers = [h for row in grid for h in row]
        hedge = True if cfg.hedge_delay_ms is None \
            else HedgePolicy(delay_s=cfg.hedge_delay_ms / 1e3)
        try:
            self.store = connect_replicated(
                grid, store_cfg, journal=journal,
                partition=cfg.partition, query_impl=cfg.query_impl,
                timeout=cfg.query_timeout_s, hedge=hedge)
        except BaseException:
            if journal is not None:
                journal.close()
            for h in self._workers:        # no orphan worker processes
                h.terminate()
            raise
        if cfg.supervisor and cfg.n_replicas > 1:
            self._supervisor = Supervisor(self.store,
                                          probe_impl=cfg.probe_impl,
                                          query_impl=cfg.query_impl)
            self._supervisor.start()

    # -- the fused fast path -----------------------------------------------
    @property
    def packed_ingest(self) -> bool:
        """Whether the fused sign->pack path serves this config (band
        boundaries fall on word boundaries; always true at b = 32)."""
        return self.cfg.rows_per_band % (32 // self.cfg.b) == 0

    def _sign(self, data, layout: str):
        """Dispatch signing for one batch (async — returns a device array,
        packed words on the fused path, raw signatures otherwise)."""
        pack_b = self.cfg.b if self.packed_ingest else None
        return self.engine.sign(jnp.asarray(data), layout=layout,
                                pack_b=pack_b)

    def _scatter(self, signed: np.ndarray) -> None:
        if self.packed_ingest:
            self.store.add_packed(signed)
        else:
            self.store.add(signed)

    # -- indexing ----------------------------------------------------------
    def add_sparse(self, idx: np.ndarray) -> None:
        self._scatter(np.asarray(self._sign(idx, "sparse")))

    def add_dense(self, v: np.ndarray) -> None:
        self._scatter(np.asarray(self._sign(v, "dense")))

    def pipeline(self, *, depth: int = 2,
                 layout: str = "sparse") -> "IngestPipeline":
        """A double-buffered ingest session over this service's store."""
        return IngestPipeline(self, depth=depth, layout=layout)

    def stream(self, **kw):
        """A streaming front end over this service: individual queries in,
        coalesced batches through the pipelined query path (see
        ``serve.stream.StreamingQueryService`` for the knobs)."""
        from repro.serve.stream import StreamConfig, StreamingQueryService
        return StreamingQueryService(self, StreamConfig(**kw))

    @property
    def size(self) -> int:
        return self.store.size

    # -- querying ----------------------------------------------------------
    def query_sparse(self, idx: np.ndarray, top_k: int = 10):
        return self._traced_query(idx, "sparse", top_k)

    def query_dense(self, v: np.ndarray, top_k: int = 10):
        return self._traced_query(v, "dense", top_k)

    def _traced_query(self, data, layout: str, top_k: int):
        """The traced front door: the root span opens here (where the
        sampling decision is made), the sign leg is its first child, and
        everything under ``_query`` — fold, broadcast, per-shard partials
        (worker-side over tcp), merge — nests beneath it, stitching one
        cross-process trace per sampled query batch."""
        with self._t_query as root:
            root.tag("n", len(data)).tag("top_k", top_k)
            with self._t_sign:
                qsigned = self._sign(data, layout)
                if not (self.packed_ingest and self.cfg.query_impl != "host"):
                    # legacy paths want the host batch here; the fused path
                    # keeps it device-resident into the store's fold and
                    # syncs only for the shard broadcast
                    qsigned = np.asarray(qsigned)
            return self._query(qsigned, top_k)

    def _query(self, qsigned: np.ndarray, top_k: int):
        """Returns (ids (Q, top_k) int64 [-1 pad], scores (Q, top_k) f32).

        Queries with no bucket hit in any shard fall back to brute force
        over the whole index — independently per query (a query with
        candidates keeps its bucket-restricted ranking)."""
        if self.store.size <= 0:
            raise ValueError(
                "query on an empty index: add documents before querying "
                "(the brute-force fallback has nothing to score)")
        if self.packed_ingest:
            return self.store.query_packed(qsigned, top_k)
        return self.store.query(qsigned, top_k)

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Shut down shard workers (tcp transport); idempotent, inproc no-op.

        Graceful first (SHUTDOWN over the wire), then a hard terminate for
        any worker that did not exit in time.  The supervisor stops FIRST —
        otherwise it would diagnose the shutdown as a mass failure and
        respawn every worker the teardown just killed.
        """
        if self._supervisor is not None:
            self._supervisor.stop()
            self._supervisor = None
        if self._workers:
            from repro.transport import shutdown_plane
            shutdown_plane(self.store, self._workers)
            # replaced workers (supervisor respawns) may not be in the
            # original list; the store's lanes are authoritative
            for rset in getattr(self.store, "shards", []):
                for lane in getattr(rset, "lanes", []):
                    if lane.handle is not None:
                        lane.handle.terminate()
        journal = getattr(self.store, "journal", None)
        if journal is not None:
            journal.close()
        self._workers = []

    def __enter__(self) -> "SimilaritySearchService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class IngestPipeline:
    """Double-buffered ingest: sign batch N+1 while batch N scatters.

    ``submit(batch)`` dispatches JAX signing for the batch (asynchronous —
    no ``np.asarray`` sync) and enqueues the device array; once ``depth``
    batches are in flight, the oldest is drained: its words are
    materialized (waiting only for whatever device work is still
    outstanding) and scattered into the shards.  While that host-side
    scatter runs — LSH insert for in-process shards, the ADD fan-out for
    tcp shards — the younger batches' signing keeps executing in the
    background, so the signing engine never sits idle between batches.

    ``depth`` is the maximum number of signed-but-unscattered batches in
    flight: ``depth=1`` is the serial path (sign, wait, scatter —
    bit-identical answers, no overlap), ``depth=2`` is classic double
    buffering, higher depths only add device-memory pressure unless
    scatter time varies a lot between batches.  Scatter order always
    equals submit order, so for ANY depth the store state — ids, buckets,
    spills — is bit-identical to serial ingestion of the same batches.

    ``flush()`` (or leaving the context) drains everything still queued.

    The wall-time split lives in the process registry as per-batch latency
    HISTOGRAMS — ``ingest.sign`` (dispatch), ``ingest.wait`` (device sync —
    small when scatter covered the compute), ``ingest.scatter`` (store
    writes), ``ingest.wall`` — each taken by the ``obs.trace.Timer`` of the
    same name, which also marks the leg on a profiler trace — so tail
    behavior (one slow scatter among hundreds) is visible, not averaged
    away.  ``timings`` is a compatibility view over the same observations:
    the familiar ``{sign_s, wait_s, scatter_s, wall_s, n_batches,
    n_items}`` dict, scoped to THIS pipeline by registry deltas from its
    construction (counts are plain ints, so ``timings["n_items"]`` works
    even with the registry disabled).
    """

    _STAGES = ("sign", "wait", "scatter", "wall")

    def __init__(self, service: SimilaritySearchService, *, depth: int = 2,
                 layout: str = "sparse"):
        if depth < 1:
            raise ValueError(f"depth must be >= 1 (got {depth})")
        if layout not in ("sparse", "dense"):
            raise ValueError(f"unknown layout {layout!r}")
        self.service = service
        self.depth = depth
        self.layout = layout
        self._inflight: collections.deque = collections.deque()
        reg, tracer = obs_metrics.default(), obs_trace.default()
        self._t = {s: obs_trace.Timer(f"ingest.{s}", reg, tracer)
                   for s in self._STAGES}
        self._h = {s: reg.histogram(f"ingest.{s}") for s in self._STAGES}
        self._base = {s: self._h[s].sum for s in self._STAGES}
        self.n_batches = 0
        self.n_items = 0

    @property
    def timings(self) -> dict:
        """The classic accumulated split, derived from the registry
        histograms (sums since this pipeline was constructed)."""
        out = {f"{s}_s": self._h[s].sum - self._base[s]
               for s in self._STAGES}
        out["n_batches"] = self.n_batches
        out["n_items"] = self.n_items
        return out

    def __len__(self) -> int:
        return len(self._inflight)

    def submit(self, batch) -> None:
        """Sign one batch (async) and scatter whatever is due."""
        with self._t["wall"]:
            with self._t["sign"]:
                signed = self.service._sign(batch, self.layout)
            self._inflight.append((signed, len(batch)))
            while len(self._inflight) >= self.depth:
                self._drain_one()

    def _drain_one(self) -> None:
        signed, n = self._inflight.popleft()
        with self._t["wait"]:
            host = np.asarray(signed)      # sync: outstanding device work
        with self._t["scatter"]:
            self.service._scatter(host)
        self.n_batches += 1
        self.n_items += n

    def flush(self) -> None:
        """Drain every in-flight batch (the pipeline stays usable)."""
        with self._t["wall"]:
            while self._inflight:
                self._drain_one()

    def __enter__(self) -> "IngestPipeline":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        if exc_type is None:               # don't mask the original error
            self.flush()
