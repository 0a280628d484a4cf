"""Kernel dispatch for the signing and probing hot paths (the one front door).

Every signature request — dense or sparse, engine or pipeline — lands here and
is routed to one of the implementations by shape and backend:

dense (B, D) binary:
  * ``int8``    — kernels.cminhash_kernel (int8 circulant bands in VMEM)
  * ``packed``  — kernels.cminhash_packed (uint32 bit-packed bands; interpret
                  mode only — its word blocks do not tile the TPU lanes)
  * ``ref``     — kernels.ref jnp oracle (also the fastest dense path on CPU,
                  where Pallas runs in interpret mode)
sparse (B, NNZ) padded index lists:
  * ``pallas``  — kernels.cminhash_sparse Pallas window-min kernel (TPU)
  * ``windows`` — same algorithm as compiled jnp (the CPU fast path)
  * ``gather``  — core.cminhash.cminhash_sparse O(B*nnz*K) gather loop
                  (the economical oracle; what ``use_kernel=False`` selects)

``impl="auto"`` policy: on TPU, dense picks ``int8`` and sparse picks
``pallas``.  On CPU (no real accelerator) the compiled-jnp twins win: dense
``ref``, sparse ``windows``.  ``use_kernel=False`` always forces the
reference formulation (``ref``/``gather``).  Kernels that the TPU compiler
cannot take at served sizes are never picked on TPU, each by a rule below
that names the reason (``packed`` signing, the Pallas LSH probe).

Block sizes left as ``None`` are resolved through the autotuner
(``autotune.recommend``: cached winner else heuristic; pass
``autotune_measure=True`` to sweep-and-cache on first miss).

``pack_b`` returns b-bit packed words: every signing impl packs its
(B, K) int32 mins with ``packfmt.pack_codes`` inside the same jit, so no
(B, K) int32 batch reaches the host.

``lsh_probe`` is the serving-side twin of the signing front door: the LSH
bucket-probe leg of a query batch, run on device over the table's resident
fused records (``kernels.lsh_probe``: Pallas kernel + compiled-jnp twin).
``impl="auto"`` picks the compiled-jnp twin on TPU (see
``select_probe_impl`` for why not the Pallas kernel) and defers to the numpy
host loop otherwise (the CPU-tuned early-terminating walk in
store/table.py).

``query_fused`` is the device-resident query pipeline: uint32-lane band-hash
fold (``kernels.query_fused``, two planes, bit-identical to the host uint64
fold) -> probe meta -> ``lsh_probe`` -> packed-code top-k scoring, one
dispatch entry with no host round trip between stages.  ``impl="auto"``
picks the Pallas fold on TPU (its probe leg follows ``select_probe_impl``)
and the compiled-jnp twins elsewhere; the
legacy host fold + planner walk stays available as the reference oracle
(``impl="host"`` is the *store's* decision — this front door serves device
impls only, mirroring ``lsh_probe``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from ..core import cminhash
from ..core.permutations import apply_permutation_dense, apply_permutation_sparse
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import autotune, lsh_probe as _lsh_probe, packfmt, ref
from . import query_fused as _query_fused
from .cminhash_kernel import cminhash_pallas
from .cminhash_packed import cminhash_packed_pallas
from .cminhash_sparse import cminhash_sparse_pallas, cminhash_sparse_windows

Array = jax.Array

DENSE_IMPLS = ("auto", "int8", "packed", "ref")
SPARSE_IMPLS = ("auto", "pallas", "windows", "gather")
PROBE_IMPLS = ("auto", "numpy", "jnp", "pallas")
QUERY_IMPLS = ("auto", "jnp", "pallas", "host")


class _Family(dict):
    """``kernel.<family>.<impl>`` call counters, each bound on first use."""

    def __init__(self, reg, family: str):
        super().__init__()
        self._reg, self._prefix = reg, f"kernel.{family}."

    def __missing__(self, impl: str):
        c = self[impl] = self._reg.counter(self._prefix + impl)
        return c


class _Obs:
    """This layer's instruments, bound once per (registry, tracer): which
    impl served each call (per-resolved-impl counts: which kernel actually
    serves the fleet), the query legs' timers and the spill counter."""

    def __init__(self, reg, tracer):
        self.reg, self.tracer = reg, tracer
        (self.dense, self.sparse, self.probe, self.fold, self.query_fused,
         self.score) = (_Family(reg, f) for f in (
             "dense", "sparse", "probe", "fold", "query_fused", "score"))
        self.t_operands, self.t_spill, self.t_readback = (
            obs_trace.Timer(n, reg, tracer)
            for n in ("query.operands", "query.spill", "query.readback"))
        self.spill_rows = reg.counter("query.spill_rows")


_OBS: _Obs | None = None


def _obs() -> _Obs:
    """The instruments of the current default registry and tracer (bound
    anew only when either is swapped)."""
    global _OBS
    reg, tracer = obs_metrics.default(), obs_trace.default()
    o = _OBS
    if o is None or o.reg is not reg or o.tracer is not tracer:
        o = _OBS = _Obs(reg, tracer)
    return o


def _backend() -> str:
    return jax.default_backend()


def _interpret() -> bool:
    return _backend() != "tpu"


def select_dense_impl(d: int, *, use_kernel: bool = True,
                      backend: str | None = None) -> str:
    """Resolve impl="auto" for a dense (B, D) signing request."""
    if not use_kernel:
        return "ref"
    backend = backend or _backend()
    if backend != "tpu":
        return "ref"        # compiled jnp beats interpret-mode Pallas on CPU
    # never "packed" on TPU: its (Bt, Dt/32) word blocks are narrower than
    # the 128-lane tiling and its word window is a value-level dynamic
    # slice — the TPU compiler refuses both (cminhash_packed docstring)
    return "int8"


def select_sparse_impl(*, use_kernel: bool = True,
                       backend: str | None = None) -> str:
    """Resolve impl="auto" for a sparse signing request."""
    if not use_kernel:
        return "gather"
    backend = backend or _backend()
    return "pallas" if backend == "tpu" else "windows"


def _resolve_blocks(kind: str, b: int, d: int, k: int,
                    overrides: dict[str, int | None],
                    autotune_measure: bool, nnz: int = 0) -> dict[str, int]:
    if all(v is not None for v in overrides.values()):
        return {n: int(v) for n, v in overrides.items()}  # fully pinned
    if autotune_measure:
        blocks = autotune.measure(kind, b, d, k, nnz=nnz)
    else:
        blocks = autotune.recommend(kind, b, d, k, nnz=nnz)
    blocks = {n: blocks[n] for n in overrides}
    blocks.update({n: int(v) for n, v in overrides.items() if v is not None})
    return blocks


def signatures_dense(v: Array, pi: Array, k: int, sigma: Array | None = None,
                     *, shift_offset: int = 1, use_kernel: bool = True,
                     impl: str = "auto", block_b: int | None = None,
                     block_d: int | None = None, pack_b: int | None = None,
                     autotune_measure: bool = False) -> Array:
    """(B, D) binary -> (B, K) int32 signatures, or (B, W) uint32 packed
    words when ``pack_b`` is set."""
    if impl not in DENSE_IMPLS:
        raise ValueError(f"impl must be one of {DENSE_IMPLS} (got {impl!r})")
    if impl == "auto":
        impl = select_dense_impl(v.shape[-1], use_kernel=use_kernel)
    _obs().dense[impl].inc()
    if sigma is not None:
        v = apply_permutation_dense(v, sigma)
    b, d = v.shape

    if impl == "ref":
        sig = ref.cminhash_dense_ref(v, pi, k, shift_offset=shift_offset)
        return sig if pack_b is None else packfmt.pack_codes(sig, pack_b)

    kind = "dense_int8" if impl == "int8" else "dense_packed"
    blocks = _resolve_blocks(kind, b, d, k,
                             {"block_b": block_b, "block_d": block_d},
                             autotune_measure)
    kernel = cminhash_pallas if impl == "int8" else cminhash_packed_pallas
    return kernel(v, pi, k, shift_offset=shift_offset,
                  interpret=_interpret(), pack_b=pack_b, **blocks)


def signatures_sparse(idx: Array, pi: Array, k: int,
                      sigma: Array | None = None, *, shift_offset: int = 1,
                      use_kernel: bool = True, impl: str = "auto",
                      block_b: int | None = None, block_j: int | None = None,
                      pack_b: int | None = None,
                      autotune_measure: bool = False) -> Array:
    """(B, NNZ) padded index lists -> (B, K) int32 signatures, or (B, W)
    uint32 packed words when ``pack_b`` is set (fused sign->pack in both
    window-min kernels; only the gather oracle packs as a separate step)."""
    if impl not in SPARSE_IMPLS:
        raise ValueError(f"impl must be one of {SPARSE_IMPLS} (got {impl!r})")
    if impl == "auto":
        impl = select_sparse_impl(use_kernel=use_kernel)
    _obs().sparse[impl].inc()
    if sigma is not None:
        idx = apply_permutation_sparse(idx, sigma)
    b, nnz = idx.shape
    d = pi.shape[0]

    if impl == "gather":
        sig = cminhash.cminhash_sparse(idx, pi, k, shift_offset=shift_offset)
        return sig if pack_b is None else packfmt.pack_codes(sig, pack_b)
    if impl == "windows":
        blocks = _resolve_blocks("sparse_windows", b, d, k,
                                 {"block_j": block_j}, autotune_measure,
                                 nnz=nnz)
        return cminhash_sparse_windows(idx, pi, k, shift_offset=shift_offset,
                                       pack_b=pack_b, **blocks)
    blocks = _resolve_blocks("sparse_pallas", b, d, k,
                             {"block_b": block_b, "block_j": block_j},
                             autotune_measure, nnz=nnz)
    return cminhash_sparse_pallas(idx, pi, k, shift_offset=shift_offset,
                                  interpret=_interpret(), pack_b=pack_b,
                                  **blocks)


# -- LSH bucket probe (the serving-side device leg) ---------------------------

def select_probe_impl(backend: str | None = None) -> str:
    """Resolve impl="auto" for a bucket-probe request: the compiled-jnp
    device twin on TPU, the numpy host loop otherwise (interpret-mode Pallas
    and the jnp twin both lose to the cache-tuned early-terminating walk on
    CPU).

    Never the Pallas kernel on TPU: it holds the whole fused records table
    as one VMEM block, 4 * n_bands * n_slots * (2 + W) bytes — gigabytes at
    served sizes (2^21 slots x 32 bands x 10 words is 2.7 GB) against
    tens of MB of VMEM.  Serving the probe from a Pallas kernel needs a
    DMA-gather redesign that leaves the records in HBM."""
    backend = backend or _backend()
    return "jnp" if backend == "tpu" else "numpy"


def lsh_probe(records_dev: Array, hashes: np.ndarray, *, n_slots: int,
              max_probes: int, impl: str = "auto",
              block_e: int = 128) -> np.ndarray:
    """(Q, n_bands) uint64 band hashes -> (Q, n_bands * W) candidate ids.

    ``records_dev`` is the table's uploaded (n_bands * n_slots, 2 + W) fused
    records (``BandedLSHTable.device_records``).  The uint64 leg (base slot,
    key halves, validity) runs on host (``lsh_probe.probe_operands``);
    everything after is device work.  This front door serves the *device*
    impls only: ``impl="auto"`` here means "the device impl for this
    backend" (Pallas on TPU, the jnp twin elsewhere) — the numpy-vs-device
    decision is ``BandedLSHTable.lookup``'s (via ``select_probe_impl``),
    since the numpy walk needs the table's host state, not an upload.
    """
    if impl not in PROBE_IMPLS:
        raise ValueError(f"impl must be one of {PROBE_IMPLS} (got {impl!r})")
    if impl == "auto":
        impl = "jnp"            # the device twin on every backend (above)
    o = _obs()
    o.probe[impl].inc()
    if impl == "numpy":
        raise ValueError("impl='numpy' is BandedLSHTable.lookup's own host "
                         "loop; call the table, not the dispatch layer")
    q, nb = hashes.shape
    w = records_dev.shape[1] - 2
    with o.t_operands:
        meta = jnp.asarray(_lsh_probe.probe_operands(hashes, n_slots))
    if impl == "jnp":
        out = _lsh_probe.lsh_probe_jnp(records_dev, meta, n_slots=n_slots,
                                       max_probes=max_probes)
    else:
        out = _lsh_probe.lsh_probe_pallas(records_dev, meta, n_slots=n_slots,
                                          max_probes=max_probes,
                                          block_e=block_e,
                                          interpret=_interpret())
    with o.t_readback:
        return np.asarray(out).reshape(q, nb * w)


# -- fused device-resident query path -----------------------------------------

def select_query_impl(backend: str | None = None) -> str:
    """Resolve impl="auto" for a fused query request: the Pallas legs on a
    real accelerator, the compiled-jnp twins elsewhere.  Never "host" — the
    store decides when the legacy host fold + planner walk must run (non-pow2
    slot counts, no stored signatures, empty buffer)."""
    backend = backend or _backend()
    return "pallas" if backend == "tpu" else "jnp"


def _fold_planes(rows_hi: Array, rows_lo: Array, *, impl: str,
                 block_q: int | None,
                 autotune_measure: bool) -> tuple[Array, Array]:
    if impl == "pallas":
        q, nb, r = rows_lo.shape
        blocks = _resolve_blocks("query_fold", q, nb, r,
                                 {"block_q": block_q}, autotune_measure)
        return _query_fused.fold_planes_pallas(rows_hi, rows_lo,
                                               interpret=_interpret(),
                                               **blocks)
    return _query_fused.fold_planes_jnp(rows_hi, rows_lo)


def fold_hashes(qwords: Array, *, n_bands: int, impl: str = "auto",
                block_q: int | None = None,
                autotune_measure: bool = False) -> np.ndarray:
    """(Q, W) packed uint32 query words -> (Q, n_bands) uint64 band hashes
    via the device uint32-lane fold.  Bit-identical to
    ``core.lsh.band_hashes_packed`` — this is the coordinator's fold leg when
    hashes must come back to host anyway (broadcast to shards)."""
    if impl not in QUERY_IMPLS:
        raise ValueError(f"impl must be one of {QUERY_IMPLS} (got {impl!r})")
    if impl == "auto":
        impl = select_query_impl()
    if impl == "host":
        raise ValueError("impl='host' is core.lsh.band_hashes_packed; call "
                         "it directly, not the dispatch layer")
    o = _obs()
    o.fold[impl].inc()
    rows_hi, rows_lo = _query_fused.words_to_planes(jnp.asarray(qwords),
                                                    n_bands)
    hi, lo = _fold_planes(rows_hi, rows_lo, impl=impl, block_q=block_q,
                          autotune_measure=autotune_measure)
    with o.t_readback:
        hi, lo = np.asarray(hi), np.asarray(lo)
    return _query_fused.planes_to_hashes(hi, lo)


def query_fused(records_dev: Array, words_dev: Array, qwords: Array, *,
                n_bands: int, n_slots: int, max_probes: int, k: int, b: int,
                top_k: int, impl: str = "auto",
                hashes: np.ndarray | None = None,
                spill_lookup=None, block_q: int | None = None,
                block_e: int | None = None, autotune_measure: bool = False,
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused fold -> probe -> score over resident store state: (Q, W) packed
    query words -> ``(ids, scores, has_candidates)`` partial-top-k triple,
    bit-identical to the host-fold planner partial.

    * ``records_dev`` — the table's uploaded fused records
      (``BandedLSHTable.device_records``).
    * ``words_dev``   — the buffer's uploaded packed signature words
      (``PackedSignatureBuffer.device_words``), scored against on device.
    * ``hashes=None`` (single-store / shard-local fold): the uint32-lane
      fold runs on device and probe meta is built there too — requires
      power-of-two ``n_slots`` (callers gate; the store falls back to host).
    * ``hashes=`` host uint64 band hashes (shard workers: the coordinator
      folds ONCE and broadcasts): the fold is skipped and the probe meta
      takes the host uint64 leg (any ``n_slots``).
    * ``spill_lookup`` — optional ``hashes -> (Q, M) int64 rows`` host
      callable for the table's rare spilled keys; invoked with the (possibly
      reconstructed) host hashes and concatenated before scoring.

    Returns host arrays: ids (Q, top_k) int64 (-1 padded), scores (Q, top_k)
    float32 (NEG_INF padded), has_candidates (Q,) bool.
    """
    if impl not in QUERY_IMPLS:
        raise ValueError(f"impl must be one of {QUERY_IMPLS} (got {impl!r})")
    if impl == "auto":
        impl = select_query_impl()
    if impl == "host":
        raise ValueError("impl='host' is the store's legacy fold + planner "
                         "walk; call the store, not the dispatch layer")
    o = _obs()
    o.query_fused[impl].inc()
    # the query batch follows the store state to its device (a shard of the
    # in-process plane may live on any device of the host)
    qwords = jax.device_put(jnp.asarray(qwords), words_dev.sharding)
    q = qwords.shape[0]
    w = records_dev.shape[1] - 2

    if hashes is None:
        o.fold[impl].inc()
        rows_hi, rows_lo = _query_fused.words_to_planes(qwords, n_bands)
        hi, lo = _fold_planes(rows_hi, rows_lo, impl=impl, block_q=block_q,
                              autotune_measure=autotune_measure)
        meta = _query_fused.meta_from_planes(hi, lo, n_slots=n_slots)
        if spill_lookup is not None:   # rare host leg needs uint64 hashes
            with o.t_readback:
                hashes = _query_fused.planes_to_hashes(np.asarray(hi),
                                                       np.asarray(lo))
    else:
        with o.t_operands:
            meta = jnp.asarray(_lsh_probe.probe_operands(hashes, n_slots))

    # the probe leg takes the Pallas kernel only off TPU (interpret mode):
    # on TPU its VMEM-resident records cannot fit (select_probe_impl)
    probe = "pallas" if impl == "pallas" and _backend() != "tpu" else "jnp"
    o.probe[probe].inc()
    if probe == "pallas":
        blocks = _resolve_blocks("probe_pallas", meta.shape[0], n_slots, w,
                                 {"block_e": block_e}, autotune_measure)
        cand = _lsh_probe.lsh_probe_pallas(records_dev, meta, n_slots=n_slots,
                                           max_probes=max_probes,
                                           interpret=True, **blocks)
    else:
        cand = _lsh_probe.lsh_probe_jnp(records_dev, meta, n_slots=n_slots,
                                        max_probes=max_probes)
    cand = cand.reshape(q, n_bands * w)
    if spill_lookup is not None:
        with o.t_spill:
            spill = np.asarray(spill_lookup(hashes))
            if spill.size:
                # a widened row widens the whole batch's candidate rows:
                # score_topk compiles once per (batch, width)
                o.spill_rows.inc(int((spill >= 0).any(axis=1).sum()))
                cand = jnp.concatenate(
                    [cand, jnp.asarray(spill.astype(np.int32))], axis=1)
    o.score["jnp"].inc()
    ids, scores, has = _query_fused.score_topk(cand, words_dev, qwords,
                                               k=k, b=b, top_k=top_k)
    with o.t_readback:
        return (np.asarray(ids).astype(np.int64), np.asarray(scores),
                np.asarray(has))
