"""SketchStore — packed signature storage + vectorized LSH indexing facade.

Owns the three pieces end-to-end: a ``PackedSignatureBuffer`` (b-bit columnar
signature storage), a ``BandedLSHTable`` (open-addressing bucket arrays), and
a ``QueryPlanner`` (batched candidate scoring).  ``add`` appends a signature
batch and indexes it; ``query`` answers a query batch with top-k (id, score)
pairs; ``candidate_pairs`` serves the dedup pipeline.  ``save``/``load``
snapshot the whole store to one ``.npz``.

The table auto-rebuilds (doubling) when open addressing degrades: slot load
factor above ``rebuild_load_factor``, or spilled entries above
``rebuild_spill_fraction`` of postings.  Probe-exhaustion spills double
``n_slots``; bucket-overflow spills double ``bucket_width``.
"""

from __future__ import annotations

import dataclasses
import time
import zlib

import numpy as np

from repro.core.lsh import band_hashes, band_hashes_packed
from repro.obs import metrics as obs_metrics

from .packed import PackedConfig, PackedSignatureBuffer
from .planner import QueryPlanner
from .table import BandedLSHTable


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    k: int                          # signature length
    n_bands: int                    # LSH bands; k = n_bands * rows_per_band
    rows_per_band: int
    b: int = 32                     # stored bits per hash (32 = exact)
    n_slots: int = 2048             # initial open-addressing slots per band
    bucket_width: int = 8           # initial postings per bucket
    max_probes: int = 16            # quadratic-probe chain bound
    capacity: int = 1024            # initial packed-buffer item capacity
    rebuild_load_factor: float = 0.7
    rebuild_spill_fraction: float = 0.01
    auto_rebuild: bool = True
    store_signatures: bool = True   # False: index-only (candidate_pairs /
                                    # candidate_rows work, query() does not)

    def __post_init__(self):
        if self.n_bands * self.rows_per_band != self.k:
            raise ValueError("n_bands * rows_per_band must equal k")
        from repro.kernels import ops
        if self.b not in ops.PACK_BITS:
            raise ValueError(f"b must be one of {ops.PACK_BITS} (got {self.b})")

    # -- positional snapshot encoding (one definition: SketchStore npz and
    # the sharded-plane manifest must never drift apart field-by-field) ----
    def to_manifest(self) -> tuple[np.ndarray, np.ndarray]:
        """(int fields (10,) int64, threshold fields (2,) float64)."""
        ints = np.asarray([self.k, self.n_bands, self.rows_per_band, self.b,
                           self.n_slots, self.bucket_width, self.max_probes,
                           self.capacity, int(self.auto_rebuild),
                           int(self.store_signatures)], np.int64)
        thr = np.asarray([self.rebuild_load_factor,
                          self.rebuild_spill_fraction])
        return ints, thr

    @classmethod
    def from_manifest(cls, ints, thr) -> "StoreConfig":
        k, nb, r, b, ns, w, p, cap, auto, keep = (int(x) for x in ints[:10])
        load_f, spill_f = (float(x) for x in thr)
        return cls(k=k, n_bands=nb, rows_per_band=r, b=b, n_slots=ns,
                   bucket_width=w, max_probes=p, capacity=cap,
                   rebuild_load_factor=load_f, rebuild_spill_fraction=spill_f,
                   auto_rebuild=bool(auto), store_signatures=bool(keep))

    @classmethod
    def sized_for(cls, n_items: int, *, target_load: float = 0.5,
                  **kw) -> "StoreConfig":
        """Config pre-sized for a known corpus: slots for ~``target_load``
        per band (one-shot adds at load >~ 0.7 exhaust probe chains) and
        buffer capacity for ``n_items``."""
        n_slots = max(2048, 1 << int(np.ceil(
            np.log2(max(n_items, 1) / target_load))))
        kw.setdefault("n_slots", n_slots)
        kw.setdefault("capacity", max(n_items, 8))
        return cls(**kw)


def check_packed_banding(cfg: StoreConfig) -> None:
    """Packed banding needs every band to start on a word boundary.

    W % n_bands == 0 alone can pass on misaligned configs (pad words
    absorbing the mismatch), so this enforces the real invariant.  Shared by
    ``SketchStore`` and the coordinator side of ``ShardedSketchStore`` —
    with remote backends the coordinator folds the band hashes itself and
    must reject the same configs its workers would.
    """
    cpw = 32 // cfg.b
    if cfg.rows_per_band % cpw:
        raise ValueError(
            f"packed banding needs rows_per_band % (32/b) == 0 (got "
            f"rows_per_band={cfg.rows_per_band}, b={cfg.b}); "
            "use add()/query() on raw signatures instead")


class SketchStore:
    def __init__(self, cfg: StoreConfig, *, probe_impl: str = "auto",
                 query_impl: str = "auto"):
        from repro.kernels import dispatch
        if query_impl not in dispatch.QUERY_IMPLS:
            raise ValueError(f"query_impl must be one of "
                             f"{dispatch.QUERY_IMPLS} (got {query_impl!r})")
        self.cfg = cfg
        # probe backend for candidate generation (runtime knob, not
        # snapshotted): "auto" -> numpy host loop on CPU, device kernel on
        # TPU; see kernels/lsh_probe.py
        self.probe_impl = probe_impl
        # fused-query backend (runtime knob, not snapshotted): "auto" ->
        # device pipeline (Pallas on TPU, compiled jnp elsewhere), "host" ->
        # the legacy host fold + planner walk (the reference oracle); see
        # kernels/query_fused.py and _resolve_query_impl for the gates
        self.query_impl = query_impl
        self.buffer = PackedSignatureBuffer(PackedConfig(
            k=cfg.k, b=cfg.b,
            capacity=cfg.capacity if cfg.store_signatures else 1))
        self.table = BandedLSHTable(cfg.n_bands, n_slots=cfg.n_slots,
                                    bucket_width=cfg.bucket_width,
                                    max_probes=cfg.max_probes)
        self.planner = QueryPlanner(self.buffer)
        self.place(None)
        self.n_rebuilds = 0
        # at b < 32 sig-keys (band_hashes over raw signatures) and packed
        # keys (band_hashes_packed over truncated words) differ; the first
        # write pins the mode and mixing raises instead of silently missing
        self._band_mode: str | None = None

    def place(self, device) -> None:
        """Keep the device-resident state (packed words, LSH records) on
        ``device`` — a ``jax.Device``, or None for the default device."""
        self.device = device
        self.buffer.device = device
        self.table.device = device

    # -- sizing ------------------------------------------------------------
    @property
    def size(self) -> int:
        return self.buffer.size if self.cfg.store_signatures \
            else self.table.n_items

    @property
    def n_spilled(self) -> int:
        return self.table.n_spilled

    def _band_keys(self, mode: str, *, write: bool) -> None:
        """Pin/check the banding key mode ('sig' or 'packed'); b = 32 keys
        are identical either way so anything goes."""
        if self.cfg.b == 32:
            return
        if self._band_mode is None:
            if write:
                self._band_mode = mode
        elif self._band_mode != mode:
            raise ValueError(
                f"this b={self.cfg.b} store was built with "
                f"{self._band_mode!r} band keys; mixing in {mode!r} keys "
                "would silently miss candidates (b < 32 truncates before "
                "hashing). Use one ingest/query mode per store.")

    # -- writes ------------------------------------------------------------
    def add(self, sigs: np.ndarray) -> np.ndarray:
        """Append + index a (B, K) int32 signature batch; returns new ids."""
        self._band_keys("sig", write=True)
        sigs = np.asarray(sigs)
        self._pregrow(len(sigs))
        if self.cfg.store_signatures:
            ids = self.buffer.append(sigs)
        else:                       # index-only: skip the packed copy
            ids = np.arange(self.table.n_items,
                            self.table.n_items + len(sigs), dtype=np.int64)
        hashes = band_hashes(sigs, self.cfg.n_bands, self.cfg.rows_per_band)
        self.table.insert(hashes, ids)
        if self.cfg.auto_rebuild:
            self._maybe_rebuild()
        return ids

    def add_packed(self, words: np.ndarray) -> np.ndarray:
        """Append + index a (B, W) uint32 packed-word batch; returns new ids.

        The fused sign->pack ingest path (``SketchEngine.sign_packed``): the
        packed words are stored verbatim and band-indexed directly from the
        words (``band_hashes_packed``) — no (B, K) int32 is ever formed.  At
        b = 32 this interoperates exactly with ``add``/``query`` (identical
        bucket keys); at b < 32 the whole store must use the packed path
        (requires rows_per_band % (32/b) == 0 so bands are word-aligned).
        """
        self._check_packed_banding()
        self._band_keys("packed", write=True)
        words = np.asarray(words, np.uint32)
        self._pregrow(len(words))
        if self.cfg.store_signatures:
            ids = self.buffer.append_packed(words)
        else:
            if words.shape[1] != self.buffer.cfg.n_words:
                raise ValueError(
                    f"expected (B, {self.buffer.cfg.n_words}) words, "
                    f"got {words.shape}")
            ids = np.arange(self.table.n_items,
                            self.table.n_items + len(words), dtype=np.int64)
        self.table.insert(band_hashes_packed(words, self.cfg.n_bands), ids)
        if self.cfg.auto_rebuild:
            self._maybe_rebuild()
        return ids

    # growth caps: beyond these the spill list is the right representation
    # (a duplicate cluster larger than any sane bucket stays spilled — pairs
    # and queries handle it exactly), so geometry cannot blow up on
    # pathological input
    _MAX_BUCKET_WIDTH = 256

    def _slot_cap(self, n_items: int | None = None) -> int:
        if n_items is None:
            n_items = self.table.n_items
        target = max(self.cfg.n_slots, 4 * max(n_items, 1))
        return 1 << (target - 1).bit_length()

    def _pregrow(self, n_new: int) -> None:
        """Grow slots geometrically ahead of the projected post-batch load.

        Reactive doubling inserts the batch into a too-small table (probe
        exhaustion spills everything), then rebuilds — replaying the batch
        it just inserted, once per doubling.  Growing to the projected size
        *before* the insert replays only the already-indexed items, once,
        and the batch lands in a table at sane load.  Final geometry is the
        same power-of-two ladder the reactive loop climbs, so the exactness
        story is unchanged (candidate sets never depend on geometry).
        """
        if not self.cfg.auto_rebuild or n_new <= 0:
            return
        t = self.table
        projected = t.n_items + n_new
        # distinct keys per band <= items, so this is the load ceiling
        need = projected / self.cfg.rebuild_load_factor
        cap = self._slot_cap(projected)
        ns = t.n_slots
        while ns < need and ns < cap:
            ns *= 2
        if ns > t.n_slots:
            self.rebuild(n_slots=min(ns, cap))

    def _maybe_rebuild(self) -> None:
        # loop: one large add can overshoot a single doubling by far.  each
        # pass grows only the dimension the failure mode points at
        for _ in range(32):
            t = self.table
            postings_cap = t.n_items * t.n_bands
            too_full = t.load_factor > self.cfg.rebuild_load_factor
            too_spilled = t.n_spilled > max(
                32, self.cfg.rebuild_spill_fraction * postings_cap)
            if not (too_full or too_spilled):
                return
            grow_w = (too_spilled and not too_full and
                      t.n_spill_overflow > t.n_spill_probe)
            if grow_w:
                if t.bucket_width >= self._MAX_BUCKET_WIDTH:
                    return                 # oversized cluster: leave it spilled
                self.rebuild(bucket_width=min(t.bucket_width * 2,
                                              self._MAX_BUCKET_WIDTH))
            else:
                if t.n_slots >= self._slot_cap():
                    return
                self.rebuild(n_slots=min(t.n_slots * 2, self._slot_cap()))

    def rebuild(self, n_slots: int | None = None,
                bucket_width: int | None = None,
                max_probes: int | None = None) -> None:
        t0 = time.perf_counter()
        self.table.rebuild(n_slots=n_slots, bucket_width=bucket_width,
                           max_probes=max_probes)
        self.n_rebuilds += 1
        reg = obs_metrics.default()
        reg.counter("store.rebuilds").inc()
        reg.histogram("store.rebuild").observe(time.perf_counter() - t0)

    # -- reads -------------------------------------------------------------
    def candidate_rows_hashed(self, hashes: np.ndarray, *, mode: str = "sig",
                              spill_cap: int | None = None) -> np.ndarray:
        """(Q, n_bands) uint64 band hashes -> (Q, C) candidate ids, -1 pad.

        The hash-level core of ``candidate_rows``/``candidate_rows_packed``
        — the sharded store folds a query batch's band hashes once and
        probes every shard with them.  ``spill_cap`` bounds per-query
        spilled matches (see ``BandedLSHTable.spilled_candidates``)."""
        self._band_keys(mode, write=False)
        cand = self.table.lookup(
            hashes, impl=self.probe_impl).astype(np.int64)
        spill = self.table.spilled_candidates(hashes, cap=spill_cap)
        if spill.shape[1]:
            cand = np.concatenate([cand, spill], axis=1)
        return cand

    def candidate_rows(self, qsigs: np.ndarray, *,
                       spill_cap: int | None = None) -> np.ndarray:
        """(Q, K) signatures -> (Q, C) candidate item ids, -1 padded.

        Includes spilled entries whose recorded (band, key) matches the
        query, so the candidate set equals the reference dict-bucket path
        even with a non-empty spill."""
        qsigs = np.asarray(qsigs)
        hashes = band_hashes(qsigs, self.cfg.n_bands, self.cfg.rows_per_band)
        return self.candidate_rows_hashed(hashes, mode="sig",
                                          spill_cap=spill_cap)

    def query(self, qsigs: np.ndarray,
              top_k: int = 10) -> tuple[np.ndarray, np.ndarray]:
        """(Q, K) signatures -> (ids (Q, top_k) [-1 pad], scores (Q, top_k)).

        Candidates (incl. per-query-matched spill, capped at top_k matches
        per hot spilled key) are scored with the packed collision op;
        results are identical to the reference dict-bucket path at b=32
        except when a single spilled (band, key) group holds more than
        top_k non-tied members — the documented spill-cap trade (see
        ``BandedLSHTable.spilled_candidates``)."""
        if not self.cfg.store_signatures:
            raise RuntimeError("query() needs stored signatures; this store "
                               "was built with store_signatures=False")
        qsigs = np.asarray(qsigs)
        return self.planner.topk(
            qsigs, self.candidate_rows(qsigs, spill_cap=top_k), top_k)

    def _check_packed_banding(self) -> None:
        check_packed_banding(self.cfg)

    def candidate_rows_packed(self, qwords: np.ndarray, *,
                              spill_cap: int | None = None) -> np.ndarray:
        """``candidate_rows`` for (Q, W) packed query words (fused path)."""
        self._check_packed_banding()
        qwords = np.asarray(qwords, np.uint32)
        hashes = band_hashes_packed(qwords, self.cfg.n_bands)
        return self.candidate_rows_hashed(hashes, mode="packed",
                                          spill_cap=spill_cap)

    # -- fused device query path -------------------------------------------
    def _resolve_query_impl(self) -> str:
        """Resolve the fused-query knob against store state.  The device
        pipeline needs: power-of-two ``n_slots`` (its slot modulo is a lane
        mask), stored signatures to score against, and a non-empty buffer
        (the score kernel gathers rows).  Anything else -> "host", the
        legacy fold + planner walk."""
        impl = self.query_impl
        if impl == "auto":
            from repro.kernels.dispatch import select_query_impl
            impl = select_query_impl()
        if impl == "host":
            return "host"
        ns = self.table.n_slots
        if (ns & (ns - 1)) or not self.cfg.store_signatures \
                or not self.buffer.size:
            return "host"
        return impl

    def _fused_partial(self, qwords, top_k: int, *, impl: str,
                       hashes: np.ndarray | None):
        """Run the fused device pipeline over resident store state and wrap
        the result as a planner partial.  ``hashes=None`` folds on device
        (single-store / shard-local); shard workers pass the coordinator's
        broadcast hashes and skip the fold.  The table's rare spilled keys
        stay a host leg, invoked only when the spill is non-empty."""
        from repro.kernels import dispatch
        from .planner import TopKPartial
        spill = None
        if self.table.n_spilled:
            spill = lambda h: self.table.spilled_candidates(h, cap=top_k)
        ids, scores, has = dispatch.query_fused(
            self.table.device_records(), self.buffer.device_words(), qwords,
            n_bands=self.cfg.n_bands, n_slots=self.table.n_slots,
            max_probes=self.table.max_probes, k=self.cfg.k, b=self.cfg.b,
            top_k=top_k, impl=impl, hashes=hashes, spill_lookup=spill)
        return TopKPartial.from_device(ids, scores, has)

    def partial_topk_packed_hashed(self, hashes: np.ndarray, qwords, top_k: int,
                                   *, mode: str = "packed"):
        """Per-shard candidate partial from pre-folded band hashes: device
        probe + score when the query knob resolves to a device backend, the
        legacy host walk otherwise.  The single rewiring point both shard
        worker kinds call (``InProcessShard`` and the tcp worker)."""
        impl = self._resolve_query_impl()
        if impl == "host":
            qwords = np.asarray(qwords, np.uint32)
            return self.planner.partial_topk_packed(
                qwords, self.candidate_rows_hashed(hashes, mode=mode,
                                                   spill_cap=top_k), top_k)
        self._band_keys(mode, write=False)
        return self._fused_partial(qwords, top_k, impl=impl, hashes=hashes)

    def query_packed(self, qwords,
                     top_k: int = 10) -> tuple[np.ndarray, np.ndarray]:
        """``query`` for already-packed (Q, W) uint32 query words — the
        serving twin of ``add_packed``; at b = 32 results are identical to
        ``query`` on the raw signatures.

        When the query knob resolves to a device backend the whole pipeline
        (uint32-lane fold -> probe -> score) runs fused on device
        (``kernels.dispatch.query_fused``, bit-identical to the host path);
        the brute-force fallback for rows with no candidates anywhere stays
        a host leg either way (it is global in the sharded plane)."""
        if not self.cfg.store_signatures:
            raise RuntimeError("query_packed() needs stored signatures; this "
                               "store was built with store_signatures=False")
        impl = self._resolve_query_impl()
        if impl == "host":
            qwords = np.asarray(qwords, np.uint32)
            return self.planner.topk_packed(
                qwords, self.candidate_rows_packed(qwords, spill_cap=top_k),
                top_k)
        from .planner import finalize_topk
        self._check_packed_banding()
        self._band_keys("packed", write=False)
        part = self._fused_partial(qwords, top_k, impl=impl, hashes=None)
        em = np.flatnonzero(~part.has_candidates)
        if len(em):
            qnp = np.asarray(qwords, np.uint32)
            brute = self.planner.brute_partial_packed(qnp[em], top_k)
            part.ids[em] = brute.ids
            part.scores[em] = brute.scores
        return finalize_topk(part)

    def candidate_pairs(self) -> np.ndarray:
        """(P, 2) int64 unique (i, j), i < j, sharing >= 1 band bucket."""
        return self.table.candidate_pairs()

    def digest(self) -> dict:
        """Content digest of the signature buffer: ``{size, crc, indexed}``.

        ``crc`` is the CRC-32 of the packed rows in insertion order, so two
        stores hold bit-identical signatures iff their digests match —
        regardless of table geometry (slot count, spills), which replay or
        snapshot boot may legitimately reproduce differently.  This is the
        parity check a resynced replica must pass against a live peer
        before rejoining the fan-out (``repro.replica.supervisor``)."""
        rows = np.ascontiguousarray(self.buffer.all_packed())
        return {"size": int(self.size),
                "crc": int(zlib.crc32(rows.tobytes()) & 0xFFFFFFFF),
                "indexed": int(self.table.n_items)}

    # -- snapshots ---------------------------------------------------------
    _BAND_MODES = (None, "sig", "packed")   # snapshot encoding of _band_mode

    def save(self, path: str) -> None:
        # snapshot the LIVE table geometry, not the boot values, so load
        # rebuilds at the grown size instead of replaying every doubling
        live = dataclasses.replace(
            self.cfg, n_slots=self.table.n_slots,
            bucket_width=self.table.bucket_width,
            max_probes=self.table.max_probes)
        ints, thr = live.to_manifest()
        np.savez(path,
                 words=np.asarray(self.buffer.all_packed()),
                 cfg=np.concatenate([ints, np.asarray(
                     [self._BAND_MODES.index(self._band_mode)], np.int64)]),
                 cfg_thresholds=thr,
                 table_hashes=self.table.hash_log)

    @classmethod
    def load(cls, path: str) -> "SketchStore":
        with np.load(path) as z:
            store = cls(StoreConfig.from_manifest(z["cfg"],
                                                  z["cfg_thresholds"]))
            # pre-band-mode snapshots (10-int cfg) load with mode unset
            mode = [int(x) for x in z["cfg"][10:]]
            store._band_mode = cls._BAND_MODES[mode[0]] if mode else None
            store.buffer = PackedSignatureBuffer.from_rows(
                store.buffer.cfg, z["words"])
            store.planner = QueryPlanner(store.buffer)
            store.place(store.device)
            hashes = z["table_hashes"]
            if len(hashes):
                store.table.insert(
                    hashes, np.arange(len(hashes), dtype=np.int64))
        return store
