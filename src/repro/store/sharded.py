"""ShardedSketchStore — the partitioned serving plane over SketchStore.

Items are partitioned across S shards, each shard a full single-host
``SketchStore`` (packed buffer + LSH table + planner).  A query batch is
folded to band hashes **once**, broadcast to every shard, and each shard
answers with a mergeable ``TopKPartial`` (candidate-restricted, local ids
mapped to global); ``distributed.collectives.merge_topk`` reduces the S
partials to the global top-k.  Because the merge order is the planner's own
(score desc, id asc) ranking, S-shard answers equal the single-shard store's
answers bit-for-bit on the same items (sole exception: the spill cap's
documented trade on oversized non-tied spilled groups, see
``BandedLSHTable.spilled_candidates``) — including the brute-force fallback:
a query row brute-forces only when it has no candidate in *any* shard (the
per-shard ``has_candidates`` votes are OR-reduced before the decision), and
the fallback leg is itself a per-shard brute partial + merge.

Where a shard *lives* is behind the ``ShardBackend`` protocol:

  * ``InProcessShard`` — the shard's ``SketchStore`` in this process (the
    default; what PR 3 ran inline);
  * ``transport.client.RemoteShard`` — the same operations against a shard
    worker process over the framed TCP wire protocol.

The coordinator keeps only cfg + partition + gid maps and never scores
anything itself, so the two backends are interchangeable per shard and the
answers are bit-identical either way — the backend moves *where* the
per-shard legs run, never *what* they compute.  The query path is split
into ``start_query``/``start_brute`` (submit) and ``Pending.result()``
(gather) so remote shards all compute concurrently under the client's
fan-out loop; in-process shards evaluate lazily at gather time.

Partitioning: ``"round_robin"`` (global id mod S — balanced for streaming
ingest) or ``"hash"`` (Fibonacci-hash of the global id — stable placement
under resharding-style workflows).  Either way global ids are assigned in
arrival order (0..N-1), identical to the single-shard store, and each shard
keeps a local->global id map.  Both partitioners append gids in ascending
order, so a shard's local rank order IS its global id order — per-shard
score-tie breaks (smaller local id first) map to smaller-global-id first,
which is what makes the merge bit-exact.

Device residency: in-process shard i keeps its packed words and LSH records
on local device ``i mod n`` (``shard_device``), so on a four-chip host an
S=4 plane holds each shard's state on its own chip; on one device every
shard shares it.

``save``/``load`` snapshot the whole plane to a directory: one
``SketchStore`` npz per shard plus a manifest (cfg, n_shards, partition,
gid maps).  Shard workers boot from the same per-shard files
(``transport.server.spawn_workers(snapshot_dir=...)``), and ``load`` with
remote backends restores just the coordinator state.
"""

from __future__ import annotations

import json
import os
import time
from typing import Protocol

import jax.numpy as jnp
import numpy as np

from repro.core.lsh import band_hashes, band_hashes_packed
from repro.distributed.collectives import merge_topk
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from ._growth import grown
from .planner import TopKPartial, finalize_topk
from .store import SketchStore, StoreConfig, check_packed_banding

_GOLD = np.uint64(0x9E3779B97F4A7C15)    # Fibonacci hashing multiplier

PARTITIONS = ("round_robin", "hash")

MANIFEST_FILE = "manifest.npz"


def shard_snapshot_path(dirpath: str, shard: int) -> str:
    """Per-shard ``SketchStore`` snapshot inside a plane snapshot dir."""
    return os.path.join(dirpath, f"shard_{shard}.npz")


def shard_partial_hist_name(shard: int) -> str:
    """Registry name of shard ``i``'s reply-latency histogram — the
    per-shard skew signal.  The transport's hedge delay derives from the
    same observation stream (``FanoutGroup`` keeps a private per-connection
    copy so co-resident planes can't pollute each other's signal); bench
    and ops tooling read the registry histograms by this name."""
    return f"query.shard{shard}.partial"


def shard_device(shard: int):
    """Where in-process shard ``shard`` keeps its device state: device
    ``shard mod n`` of this host's n local devices, so an S-shard plane on
    a four-chip host holds each shard's words and records on its own chip.
    None (the default device) on a one-device host."""
    import jax
    devices = jax.local_devices()
    return devices[shard % len(devices)] if len(devices) > 1 else None


# -- the backend seam ---------------------------------------------------------

class Pending(Protocol):
    """Handle for one submitted per-shard query leg."""

    def result(self) -> TopKPartial: ...


class ShardBackend(Protocol):
    """One shard of the serving plane, wherever it lives.

    The contract mirrors what the coordinator needs and nothing more:
    writes route a partitioned batch (local ids are assigned worker-side in
    arrival order, exactly like ``SketchStore``) and are a submit/gather
    pair like queries (``start_add``) so S shards index concurrently;
    queries are a submit/gather pair so S shards can compute concurrently,
    and partials come back in local ids (the coordinator owns the gid
    maps).
    """

    def add(self, sigs: np.ndarray) -> int: ...
    def add_packed(self, words: np.ndarray) -> int: ...
    def start_add(self, batch: np.ndarray, *, packed: bool) -> Pending: ...
    def start_query(self, hashes: np.ndarray, qwords: np.ndarray,
                    top_k: int, mode: str) -> Pending: ...
    def start_brute(self, qwords: np.ndarray, top_k: int) -> Pending: ...
    def stats(self) -> dict: ...
    def save(self, path: str) -> None: ...
    def close(self) -> None: ...


class _Lazy:
    """In-process Pending: evaluate at gather time (mirrors the remote
    submit/gather split so fan-out timing buckets mean the same thing).

    ``lazy = True`` is the write path's no-work-until-read guarantee: a
    lazy ADD pending that is never gathered provably never touched its
    store (a remote pending's work runs worker-side whether or not the
    reply is read) — ``_scatter`` uses this to keep a clean first failure
    from poisoning the plane."""

    lazy = True

    def __init__(self, fn):
        self._fn = fn
        self.latency_s: float | None = None     # thunk runtime, once gathered

    def result(self) -> TopKPartial:
        t0 = time.perf_counter()
        try:
            return self._fn()
        finally:
            self.latency_s = time.perf_counter() - t0


class InProcessShard:
    """``ShardBackend`` over a local ``SketchStore`` (the classic path)."""

    def __init__(self, cfg: StoreConfig | None = None, *,
                 probe_impl: str | None = None,
                 query_impl: str | None = None,
                 store: SketchStore | None = None, device=None):
        if store is None:
            if cfg is None:
                raise ValueError("InProcessShard needs cfg or store")
            store = SketchStore(cfg, probe_impl=probe_impl or "auto",
                                query_impl=query_impl or "auto")
        else:                            # never clobber a configured store
            if probe_impl is not None:
                store.probe_impl = probe_impl
            if query_impl is not None:
                store.query_impl = query_impl
        if device is not None:
            store.place(device)
        self.store = store

    def _add(self, fn, batch) -> int:
        # tag exceptions that left the store partially mutated (append
        # landed, insert raised) so _scatter knows a retry would duplicate
        before = (self.store.size, self.store.table.n_items)
        try:
            return len(fn(batch))
        except BaseException as e:
            if (self.store.size, self.store.table.n_items) != before:
                e.dirty = True
            raise

    def add(self, sigs: np.ndarray) -> int:
        return self._add(self.store.add, sigs)

    def add_packed(self, words: np.ndarray) -> int:
        return self._add(self.store.add_packed, words)

    def start_add(self, batch: np.ndarray, *, packed: bool = False) -> _Lazy:
        # routes through self.add/add_packed (not the store directly) so
        # subclass overrides keep intercepting the write path
        fn = self.add_packed if packed else self.add
        return _Lazy(lambda: fn(batch))

    def start_query(self, hashes: np.ndarray, qwords: np.ndarray,
                    top_k: int, mode: str) -> _Lazy:
        # the store routes to the fused device pipeline or the legacy host
        # walk per its query_impl knob — bit-identical either way
        return _Lazy(lambda: self.store.partial_topk_packed_hashed(
            hashes, qwords, top_k, mode=mode))

    def start_brute(self, qwords: np.ndarray, top_k: int) -> _Lazy:
        return _Lazy(lambda: self.store.planner.brute_partial_packed(
            qwords, top_k))

    def stats(self) -> dict:
        from repro.kernels.dispatch import select_probe_impl, \
            select_query_impl
        impl = self.store.probe_impl
        if impl == "auto":                   # report what auto resolves to
            impl = select_probe_impl()
        qimpl = self.store.query_impl
        if qimpl == "auto":
            qimpl = select_query_impl()
        return {"size": self.store.size, "n_spilled": self.store.n_spilled,
                "n_rebuilds": self.store.n_rebuilds, "probe_impl": impl,
                "query_impl": qimpl}

    def save(self, path: str) -> None:
        self.store.save(path)

    def close(self) -> None:
        pass


class ShardedSketchStore:
    """S-way partitioned SketchStore with exact global top-k.

    ``n_shards=1`` degenerates to a thin wrapper over one ``SketchStore``
    (same ids, same scores, same fallback behavior), so serving configs keep
    a single code path and raise ``n_shards`` when one host's table or
    buffer stops fitting.  Pass ``backends`` (e.g. ``RemoteShard``s from
    ``transport.client``) to run the same plane over shard worker
    processes; the default builds ``InProcessShard``s.
    """

    def __init__(self, cfg: StoreConfig, n_shards: int = 1, *,
                 partition: str = "round_robin", probe_impl: str = "auto",
                 query_impl: str = "auto", backends: list | None = None):
        if backends is not None:
            if not backends:
                raise ValueError("backends must be non-empty")
            n_shards = len(backends)
        if n_shards <= 0:
            raise ValueError("n_shards must be positive")
        if partition not in PARTITIONS:
            raise ValueError(f"partition must be one of {PARTITIONS} "
                             f"(got {partition!r})")
        self.cfg = cfg
        self.n_shards = n_shards
        self.partition = partition
        # fused-query knob: shards apply it to their probe+score legs; the
        # coordinator applies it to its one broadcast fold (remote backends
        # got their own copy at spawn time — see transport.server)
        self.query_impl = query_impl
        self.shards = backends if backends is not None else [
            InProcessShard(cfg, probe_impl=probe_impl, query_impl=query_impl,
                           device=shard_device(i))
            for i in range(n_shards)]
        # local->global id map per shard (amortized-doubling append buffer)
        self._gid_buf = [np.zeros(8, np.int64) for _ in range(n_shards)]
        self._gid_len = [0] * n_shards
        self.n_items = 0
        # wall-time split of the last query: submit/serialize (broadcast),
        # per-shard partial compute + gather (partial), reduction (merge)
        self.last_timings: dict[str, float] = {}
        # set when a partial write left coordinator/worker state divergent
        self._failed: str | None = None
        # registry handles and timers bound once; per-shard partial-latency
        # histograms are the skew evidence load-aware rebalancing will consume
        reg, tracer = obs_metrics.default(), obs_trace.default()
        self._t_store = obs_trace.Timer("store.query", reg, tracer)
        self._t_fold = obs_trace.Timer("query.fold", reg, tracer)
        self._t_readback = obs_trace.Timer("query.readback", reg, tracer)
        self._t_wall = obs_trace.Timer("query.wall", reg, tracer)
        self._t_broadcast = obs_trace.Timer("query.broadcast", reg, tracer)
        self._t_partial = obs_trace.Timer("query.partial", reg, tracer)
        self._t_merge = obs_trace.Timer("query.merge", reg, tracer)
        self._t_brute = obs_trace.Timer("query.brute", reg, tracer)
        self._c_brute_rows = reg.counter("query.brute_rows")
        self._h_shard = [reg.histogram(shard_partial_hist_name(i))
                         for i in range(n_shards)]

    # -- sizing ------------------------------------------------------------
    @property
    def size(self) -> int:
        return self.n_items

    @property
    def n_spilled(self) -> int:
        return sum(s.stats()["n_spilled"] for s in self.shards)

    def shard_sizes(self) -> np.ndarray:
        return np.asarray([s.stats()["size"] for s in self.shards], np.int64)

    def obs_snapshot(self) -> dict:
        """One merged registry snapshot for the whole plane: the
        coordinator's own registry plus every remote worker's (the ``obs``
        JSON in their STATS replies), reduced with ``merge_snapshots`` —
        the same exact associative reduction ``merge_topk`` does for
        scores.  In-process shards already share the coordinator's
        registry, so their stats carry no ``obs`` and nothing is counted
        twice.

        Every worker snapshot is merged twice: raw on the shard's FIRST
        lane only (plane-wide totals keep meaning "one lane per shard" at
        any replication factor, so dashboards and the existing assertions
        survive R>1 unchanged) and under a ``shard{i}.replica{r}.`` prefix
        for every lane (``label_snapshot``) — the provenance a failover
        investigation needs to see which replica's counters moved.
        Backends exposing ``stats_all`` (replica sets) contribute one
        labelled snapshot per live lane; plain backends are lane
        ``replica 0`` of their shard."""
        snaps = [obs_metrics.default().snapshot()]
        for i, sh in enumerate(self.shards):
            stats_all = getattr(sh, "stats_all", None)
            per_lane = stats_all() if stats_all is not None \
                else [(0, sh.stats())]
            for k, (r, stats) in enumerate(per_lane):
                blob = stats.get("obs")
                if not blob:
                    continue
                snap = json.loads(blob) if isinstance(blob, str) else blob
                if k == 0:
                    snaps.append(snap)
                snaps.append(obs_metrics.label_snapshot(
                    snap, f"shard{i}.replica{r}."))
        return obs_metrics.merge_snapshots(*snaps)

    def _gids(self, shard: int) -> np.ndarray:
        return self._gid_buf[shard][: self._gid_len[shard]]

    # -- partitioning ------------------------------------------------------
    def _shard_of(self, gids: np.ndarray) -> np.ndarray:
        if self.partition == "round_robin":
            return gids % self.n_shards
        with np.errstate(over="ignore"):
            h = gids.astype(np.uint64) * _GOLD
        return ((h >> np.uint64(33)) % np.uint64(self.n_shards)) \
            .astype(np.int64)

    def _check_consistent(self) -> None:
        if self._failed:
            raise RuntimeError(
                f"plane is inconsistent after a failed add ({self._failed}); "
                "rebuild it or reload from the last snapshot")

    def _scatter(self, batch: np.ndarray, *, packed: bool) -> np.ndarray:
        """Assign global ids, fan batch slices out to all shards, record
        the maps.

        Writes fan out like queries: every shard's slice is submitted first
        (``start_add``), then gathered — remote shards index concurrently
        over the wire instead of one blocking request per shard, which is
        what closes the tcp-vs-inproc build gap.

        A batch is all-or-nothing at the coordinator: if any shard indexed
        its slice while another failed, or a failing shard reports a
        partial write (``e.dirty``), or the fan-out broke after frames hit
        the wire (``e.unknown_outcome`` — nobody can prove which workers
        processed their slice), retrying would re-issue the same gids and
        duplicate rows — so the plane is marked inconsistent and refuses
        further writes and reads instead of silently double-indexing.  A
        failure that provably left every shard unwritten (validation
        ERROR replies, a submit-phase failure before any frame was sent,
        an in-process exception with no earlier shard evaluated) leaves
        the plane usable.
        """
        self._check_consistent()
        n = len(batch)
        gids = np.arange(self.n_items, self.n_items + n, dtype=np.int64)
        owner = self._shard_of(gids)
        # submit phase: remote backends only queue frames here (the first
        # gather drives the sockets), in-process backends build thunks — a
        # submit failure abandons the queued round before anything is sent,
        # so the plane stays usable
        pend = []
        for s in range(self.n_shards):
            sel = np.flatnonzero(owner == s)
            if len(sel):
                pend.append((s, sel,
                             self.shards[s].start_add(batch[sel],
                                                      packed=packed)))
        # gather phase: consume EVERY pending (remote slices run worker-side
        # whether or not their reply is read), then decide poisoning from
        # the full outcome set.  Lazy in-process pendings after a failure
        # are skipped — never evaluated, provably never written.
        wrote_any = False
        sure_clean = True       # every failure provably left stores unwritten
        first_err: BaseException | None = None
        for s, sel, p in pend:
            if first_err is not None and getattr(p, "lazy", False):
                continue
            try:
                added = p.result()
                wrote_any = True
                if added != len(sel):
                    raise RuntimeError(
                        f"shard {s} indexed {added} of {len(sel)} rows")
            except BaseException as e:
                if getattr(e, "dirty", False) or \
                        getattr(e, "unknown_outcome", False):
                    sure_clean = False
                if first_err is None:
                    first_err = e
                continue
            need = self._gid_len[s] + len(sel)
            self._gid_buf[s] = grown(self._gid_buf[s], need)
            self._gid_buf[s][self._gid_len[s]: need] = gids[sel]
            self._gid_len[s] = need
        if first_err is not None:
            if wrote_any or not sure_clean:
                self._failed = f"{type(first_err).__name__} mid-batch"
            raise first_err
        self.n_items += n
        return gids

    # -- writes ------------------------------------------------------------
    def add(self, sigs: np.ndarray) -> np.ndarray:
        """Partition + index a (B, K) int32 signature batch; returns the
        global ids (assigned in arrival order, same as one SketchStore)."""
        return self._scatter(np.asarray(sigs), packed=False)

    def add_packed(self, words: np.ndarray) -> np.ndarray:
        """``add`` for (B, W) uint32 fused sign->pack words."""
        return self._scatter(np.asarray(words, np.uint32), packed=True)

    # -- reads -------------------------------------------------------------
    def _to_global(self, shard: int, part: TopKPartial) -> TopKPartial:
        """Map a shard partial's local ids to global ids.  The gid map is
        monotone (both partitioners append ascending gids), so rows stay in
        (score desc, id asc) order — no re-sort needed before the merge."""
        gid = self._gids(shard)
        if not len(gid):              # empty shard: partial is all padding
            return part
        hit = part.ids >= 0
        ids = np.where(hit, gid[np.where(hit, part.ids, 0)], np.int64(-1))
        return TopKPartial(ids, part.scores, part.has_candidates)

    def _fanout(self, start, tally: dict) -> list[TopKPartial]:
        """One submit/gather round over all shards, timed into ``tally``.

        Per-shard reply latencies land in the ``query.shard{i}.partial``
        histograms: for remote backends the offset from fan-out start to
        that shard's reply frame completing, for in-process backends the
        thunk runtime — either way, how long shard i made the round wait.
        The broadcast span is ambient while legs are submitted, so remote
        workers' spans nest under it in the stitched trace.
        """
        with self._t_broadcast:
            pend = [start(sh) for sh in self.shards]
        with self._t_partial:
            parts = [self._to_global(s, p.result())
                     for s, p in enumerate(pend)]
        tally["broadcast_s"] += self._t_broadcast.last
        tally["partial_s"] += self._t_partial.last
        for s, p in enumerate(pend):
            lat = getattr(p, "latency_s", None)
            if lat is not None:
                self._h_shard[s].observe(lat)
        return parts

    def _merged_query(self, hashes: np.ndarray, qwords: np.ndarray,
                      top_k: int, mode: str, fold_s: float = 0.0,
                      ) -> tuple[np.ndarray, np.ndarray]:
        """The shared scoring core: per-shard candidate partials -> merge ->
        global brute-force leg for rows with no candidates anywhere.
        ``fold_s`` is the caller's already-spent band-hash fold time, folded
        into the timing split so every query stage is accounted for."""
        with self._t_wall:
            tally = {"fold_s": fold_s, "broadcast_s": 0.0, "partial_s": 0.0,
                     "merge_s": 0.0}
            parts = self._fanout(
                lambda sh: sh.start_query(hashes, qwords, top_k, mode), tally)
            has_any = np.zeros(len(qwords), bool)
            for p in parts:
                has_any |= p.has_candidates
            em = np.flatnonzero(~has_any)
            brute = None
            if len(em) and self.n_items:
                self._c_brute_rows.inc(len(em))
                with self._t_brute:
                    brute = self._fanout(
                        lambda sh: sh.start_brute(qwords[em], top_k), tally)
            with self._t_merge:
                scores, ids = merge_topk([p.scores for p in parts],
                                         [p.ids for p in parts], top_k)
                if brute is not None:
                    scores[em], ids[em] = merge_topk(
                        [p.scores for p in brute], [p.ids for p in brute],
                        top_k)
            tally["merge_s"] = self._t_merge.last
            self.last_timings = tally
        return finalize_topk(TopKPartial(ids, scores, has_any))

    def query(self, qsigs: np.ndarray,
              top_k: int = 10) -> tuple[np.ndarray, np.ndarray]:
        """(Q, K) signatures -> (ids (Q, top_k) [-1 pad], scores (Q, top_k)).

        Bit-identical to single-shard ``SketchStore.query`` on the same
        items, for any shard count, either partitioner, and either
        backend."""
        self._check_queryable("query()")
        qsigs = np.asarray(qsigs)
        # store.query is the root when nobody upstream opened one (a direct
        # store caller still gets one stitched trace); under the service's
        # "service.query" span it just nests
        with self._t_store:
            with self._t_fold:
                hashes = band_hashes(qsigs, self.cfg.n_bands,
                                     self.cfg.rows_per_band)
                qwords = np.asarray(
                    ops.pack_codes(jnp.asarray(qsigs, jnp.int32), self.cfg.b))
            return self._merged_query(hashes, qwords, top_k, "sig",
                                      fold_s=self._t_fold.last)

    def query_packed(self, qwords: np.ndarray,
                     top_k: int = 10) -> tuple[np.ndarray, np.ndarray]:
        """``query`` for already-packed (Q, W) uint32 query words.

        The coordinator folds band hashes ONCE for the whole plane; per the
        ``query_impl`` knob that fold runs through the device uint32-lane
        kernel (``dispatch.fold_hashes``, bit-identical) or the host uint64
        loop.  A device-resident query batch (the fused serving path) is
        folded as-is — the one host sync is the broadcast copy the wire
        needs anyway."""
        self._check_queryable("query_packed()")
        check_packed_banding(self.cfg)
        with self._t_store:
            with self._t_fold:
                hashes = self._fold_packed(qwords)
            fold_s = self._t_fold.last
            with self._t_readback:
                qwords = np.asarray(qwords, np.uint32)
            return self._merged_query(hashes, qwords, top_k, "packed",
                                      fold_s=fold_s)

    def _fold_packed(self, qwords) -> np.ndarray:
        impl = self.query_impl
        if impl == "auto":
            from repro.kernels.dispatch import select_query_impl
            impl = select_query_impl()
        if impl != "host":
            from repro.kernels.dispatch import fold_hashes
            return fold_hashes(qwords, n_bands=self.cfg.n_bands, impl=impl)
        return band_hashes_packed(np.asarray(qwords, np.uint32),
                                  self.cfg.n_bands)

    def _check_queryable(self, op: str) -> None:
        self._check_consistent()
        if not self.cfg.store_signatures:
            raise RuntimeError(f"{op} needs stored signatures; this store "
                               "was built with store_signatures=False")

    def candidate_pairs(self) -> np.ndarray:
        """Dedup-path pairs — single-shard only: a partitioned index never
        co-buckets items from different shards, so cross-shard pairs would
        be silently missed.  Run dedup on a 1-shard store."""
        if self.n_shards != 1:
            raise NotImplementedError(
                "candidate_pairs() is exact only at n_shards=1 (cross-shard "
                "pairs never share a shard-local bucket); run dedup on a "
                "single-shard store")
        if not isinstance(self.shards[0], InProcessShard):
            raise NotImplementedError(
                "candidate_pairs() needs the shard's table in-process; "
                "load the snapshot into an InProcessShard store for dedup")
        return self.shards[0].store.candidate_pairs()

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Release backend resources (sockets for remote shards)."""
        for sh in self.shards:
            sh.close()

    # -- snapshots ---------------------------------------------------------
    def save(self, dirpath: str) -> None:
        """Snapshot the plane: per-shard ``SketchStore`` npz + manifest.

        Remote backends write their shard file worker-side (same filesystem
        in the localhost deployment); the manifest (cfg, partition, gid
        maps) is always written here, since only the coordinator has it.
        """
        self._check_consistent()
        os.makedirs(dirpath, exist_ok=True)
        for i, sh in enumerate(self.shards):
            sh.save(shard_snapshot_path(dirpath, i))
        ints, thr = self.cfg.to_manifest()
        gids = {f"gids_{i}": self._gids(i) for i in range(self.n_shards)}
        np.savez(os.path.join(dirpath, MANIFEST_FILE),
                 n_shards=self.n_shards, n_items=self.n_items,
                 partition=self.partition, cfg=ints, cfg_thresholds=thr,
                 **gids)

    @classmethod
    def load(cls, dirpath: str, *, backends: list | None = None,
             probe_impl: str = "auto",
             query_impl: str = "auto") -> "ShardedSketchStore":
        """Restore a plane snapshot.

        Default: every shard is loaded into an ``InProcessShard``.  With
        ``backends`` (remote shards already booted from the same snapshot
        via ``spawn_workers(snapshot_dir=...)``), only the coordinator
        state — cfg, partition, gid maps — is restored here.
        """
        with np.load(os.path.join(dirpath, MANIFEST_FILE)) as z:
            n_shards = int(z["n_shards"])
            n_items = int(z["n_items"])
            partition = str(z["partition"])
            cfg = StoreConfig.from_manifest(z["cfg"], z["cfg_thresholds"])
            gids = [np.asarray(z[f"gids_{i}"], np.int64)
                    for i in range(n_shards)]
        if backends is None:
            backends = [
                InProcessShard(store=SketchStore.load(
                    shard_snapshot_path(dirpath, i)), probe_impl=probe_impl,
                    query_impl=query_impl, device=shard_device(i))
                for i in range(n_shards)]
        elif len(backends) != n_shards:
            raise ValueError(f"snapshot has {n_shards} shards, got "
                             f"{len(backends)} backends")
        store = cls(cfg, n_shards, partition=partition, backends=backends,
                    query_impl=query_impl)
        for i, g in enumerate(gids):
            store._gid_buf[i] = grown(store._gid_buf[i], len(g))
            store._gid_buf[i][: len(g)] = g
            store._gid_len[i] = len(g)
        store.n_items = n_items
        return store
