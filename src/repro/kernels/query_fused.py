"""Device-resident query pipeline: fold -> probe -> score without host hops.

Before this module a query batch bounced host<->device three times: the
band-hash fold ran on host in uint64 (``core.lsh._poly_fold``), the probe
either ran on host or shipped its candidates back, and scoring gathered
candidate rows through numpy.  These pieces keep a query batch on the
accelerator from packed words to ranked (id, score) rows:

* **uint32-lane fold** — JAX's default domain is 32-bit and XLA has no
  uint64 on most backends, so the polynomial fold is emulated on two uint32
  planes (``lo``/``hi``), carry-correct through the 64-bit multiply
  (16-bit limb decomposition), the ``+ x + 1`` double carry, and the
  ``h ^= h >> 29`` cross-plane shift.  Bit-identical to the host fold for
  every input — including negative int32 signature codes, whose host-side
  ``astype(np.uint64)`` sign-extends (the ``hi`` plane is all-ones there).
  Both a Pallas kernel (``fold_planes_pallas``, lane-dense entry tiles) and
  a compiled-jnp twin (``fold_planes_jnp``) are provided; parity is swept
  in tests/test_query_fused.py.
* **device probe meta** — ``meta_from_planes`` builds the ``lsh_probe``
  operand block (band offset, base slot, key halves, validity) from the
  fold planes without leaving the device.  Requires power-of-two
  ``n_slots`` so ``key % n_slots`` is ``lo & (n_slots - 1)`` (the default
  geometry and every doubling of it; non-pow2 configs take the host path).
* **fused scorer** — ``score_topk`` turns (Q, C) -1-padded candidate rows
  plus the resident packed-word buffers into ranked (Q, top_k) partials:
  sort-by-id dedup, one row gather, b-bit unpack, integer collision
  counts, and a two-key ``lax.sort`` on (count desc, id asc) — the exact
  tie-break the host planner's stable argsort produces, so fused and
  host-fold answers are bit-identical (scores are the same
  ``counts.astype(float32) / k`` division both ways).

The wire protocol already ships band hashes as two uint32 planes
(``transport.wire.split_u64``); this module is the compute-side twin of
that representation.  ``kernels.dispatch.query_fused`` is the front door
that composes these stages with the resident records/words uploads.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .lsh_probe import META_COLS
from .packfmt import unpack_codes

Array = jax.Array

# the host fold's Fibonacci multiplier, split into uint32 halves
BASE_HI = 0x9E3779B9
BASE_LO = 0x7F4A7C15

_M16 = 0xFFFF
_INVALID_ID = np.int32(2**31 - 1)   # in-scorer sentinel: sorts after real ids

# records/meta key halves use the NATIVE int32 view of the uint64 key
# (store/table.py ``_halves``): on little-endian hosts column 0 is the low
# word.  The device meta builder must agree with however the records were
# written, so the plane->column mapping follows the host byte order.
_LITTLE_ENDIAN = sys.byteorder == "little"


# -- two-plane uint64 emulation ----------------------------------------------

def _mul32_hi_lo(a: Array, b: Array) -> tuple[Array, Array]:
    """Full 64-bit product of two uint32 arrays as (hi, lo) uint32 planes.

    16-bit limb decomposition: every partial product and the carry
    accumulator fit uint32 (max (2^16-1)^2 + 2*(2^16-1) < 2^32), so no
    intermediate ever needs a wider lane."""
    m16 = jnp.uint32(_M16)
    a0, a1 = a & m16, a >> jnp.uint32(16)
    b0, b1 = b & m16, b >> jnp.uint32(16)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    mid = (p00 >> jnp.uint32(16)) + (p01 & m16) + (p10 & m16)
    lo = (p00 & m16) | (mid << jnp.uint32(16))
    hi = a1 * b1 + (p01 >> jnp.uint32(16)) + (p10 >> jnp.uint32(16)) \
        + (mid >> jnp.uint32(16))
    return hi, lo


def _fold_step(hi: Array, lo: Array, xhi: Array,
               xlo: Array) -> tuple[Array, Array]:
    """One fold round on the planes: ``h = h * BASE + x + 1; h ^= h >> 29``.

    * multiply: lo * BASE is a full 32x32->64 product; the high plane adds
      the two cross terms (wrapping, as uint64 mul does);
    * add x + 1: two carry checks — ``lo + xlo`` can wrap, and the ``+ 1``
      can wrap again when the sum landed on 0xFFFFFFFF;
    * shift-xor: ``(h >> 29).lo`` takes 3 bits from the high plane.
    """
    phi, plo = _mul32_hi_lo(lo, jnp.uint32(BASE_LO))
    phi = phi + lo * jnp.uint32(BASE_HI) + hi * jnp.uint32(BASE_LO)
    s = plo + xlo
    c1 = (s < plo).astype(jnp.uint32)
    s1 = s + jnp.uint32(1)
    c2 = (s1 == 0).astype(jnp.uint32)
    lo = s1
    hi = phi + xhi + c1 + c2
    slo = (lo >> jnp.uint32(29)) | (hi << jnp.uint32(3))
    shi = hi >> jnp.uint32(29)
    return hi ^ shi, lo ^ slo


def _fold_planes(rows_hi: Array, rows_lo: Array) -> tuple[Array, Array]:
    """(..., R) uint32 planes -> (...,) hi/lo fold planes (R unrolled)."""
    hi = jnp.zeros(rows_lo.shape[:-1], jnp.uint32)
    lo = jnp.zeros_like(hi)
    for r in range(rows_lo.shape[-1]):
        hi, lo = _fold_step(hi, lo, rows_hi[..., r], rows_lo[..., r])
    return hi, lo


def words_to_planes(words: Array, n_bands: int) -> tuple[Array, Array]:
    """(B, W) uint32 packed words -> (B, n_bands, W/n_bands) hi/lo planes.

    The packed twin of ``core.lsh.band_hashes_packed``'s reshape: words are
    non-negative 32-bit values, so the high plane is zero."""
    b, w = words.shape
    if w % n_bands:
        raise ValueError(f"W={w} not divisible by n_bands={n_bands}")
    lo = words.astype(jnp.uint32).reshape(b, n_bands, w // n_bands)
    return jnp.zeros_like(lo), lo


def sig_to_planes(sig: Array, n_bands: int,
                  rows_per_band: int) -> tuple[Array, Array]:
    """(B, K) int32 signatures -> (B, n_bands, rows_per_band) hi/lo planes.

    Matches the host fold's ``astype(np.uint64)`` on int32: negative codes
    sign-extend, so their high plane is all-ones."""
    b, k = sig.shape
    if n_bands * rows_per_band != k:
        raise ValueError(f"K={k} != n_bands*rows_per_band")
    s = sig.reshape(b, n_bands, rows_per_band)
    lo = s.astype(jnp.uint32)
    hi = jnp.where(s < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    return hi, lo


@jax.jit
def fold_planes_jnp(rows_hi: Array, rows_lo: Array) -> tuple[Array, Array]:
    """Compiled-jnp fold: (B, nb, R) uint32 planes -> (B, nb) hi/lo planes.

    Bit-identical to ``core.lsh._poly_fold`` on the joined uint64 values;
    the dispatchable device fold on CPU-hosted backends and the
    oracle-equivalent of the Pallas kernel."""
    return _fold_planes(rows_hi, rows_lo)


def _fold_kernel(hi_ref, lo_ref, out_hi_ref, out_lo_ref, *, r: int):
    hi = jnp.zeros(out_lo_ref.shape, jnp.uint32)
    lo = jnp.zeros_like(hi)
    for i in range(r):                       # static: one round per row
        hi, lo = _fold_step(hi, lo, hi_ref[i], lo_ref[i])
    out_hi_ref[...] = hi
    out_lo_ref[...] = lo


_TILE = 8 * 128                              # one (8, 128) uint32 vreg tile


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def fold_planes_pallas(rows_hi: Array, rows_lo: Array, *, block_q: int = 128,
                       interpret: bool = True) -> tuple[Array, Array]:
    """Pallas fold kernel: (B, nb, R) uint32 planes -> (B, nb) hi/lo planes.

    Every (query, band) entry folds independently, so the kernel sees the
    flat entries lane-dense: the planes go in as (R, E/128, 128) and each
    grid step folds one tile of ``block_q * nb`` entries (rounded up to
    whole (8, 128) tiles) with the R rounds statically unrolled.  Per-tile
    HBM traffic is one read of the input planes and one write of the key
    planes.  ``interpret=True`` runs on CPU."""
    q, nb, r = rows_lo.shape
    e = q * nb
    te = -(-max(1, block_q) * nb // _TILE) * _TILE
    ne = -(-e // te)

    def lanes(x):                            # (B, nb, R) -> (R, E'/128, 128)
        x = jnp.pad(x.reshape(e, r).T, ((0, 0), (0, ne * te - e)))
        return x.reshape(r, ne * te // 128, 128)

    rows = te // 128
    out_hi, out_lo = pl.pallas_call(
        functools.partial(_fold_kernel, r=r),
        grid=(ne,),
        in_specs=[pl.BlockSpec((r, rows, 128), lambda i: (0, i, 0))] * 2,
        out_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((ne * rows, 128), jnp.uint32)] * 2,
        interpret=interpret,
    )(lanes(rows_hi), lanes(rows_lo))
    return (out_hi.reshape(-1)[:e].reshape(q, nb),
            out_lo.reshape(-1)[:e].reshape(q, nb))


def planes_to_hashes(hi, lo) -> np.ndarray:
    """(Q, nb) uint32 planes -> (Q, nb) uint64 host hashes (the rare host
    leg: spill matching and the wire broadcast both want uint64)."""
    hi = np.asarray(hi, np.uint64)
    lo = np.asarray(lo, np.uint64)
    return (hi << np.uint64(32)) | lo


# -- device probe meta --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_slots",))
def meta_from_planes(hi: Array, lo: Array, *, n_slots: int) -> Array:
    """(Q, nb) fold planes -> (Q * nb, 5) int32 probe operands on device.

    The device twin of ``lsh_probe.probe_operands``: requires pow2
    ``n_slots`` (``key % n_slots == lo & (n_slots - 1)``).  Column order of
    the key halves follows the host byte order, because the records array
    the probe compares against was written through a native int32 view.
    """
    if n_slots & (n_slots - 1):
        raise ValueError(f"meta_from_planes needs pow2 n_slots (got {n_slots})")
    q, nb = lo.shape
    ones = jnp.uint32(0xFFFFFFFF)
    flat_lo = lo.reshape(-1)
    flat_hi = hi.reshape(-1)
    lin_band = jnp.tile(jnp.arange(nb, dtype=jnp.int32) * n_slots, q)
    base = (flat_lo & jnp.uint32(n_slots - 1)).astype(jnp.int32)
    klo = jax.lax.bitcast_convert_type(flat_lo, jnp.int32)
    khi = jax.lax.bitcast_convert_type(flat_hi, jnp.int32)
    valid = (~((flat_lo == ones) & (flat_hi == ones))).astype(jnp.int32)
    if not _LITTLE_ENDIAN:                      # pragma: no cover
        klo, khi = khi, klo
    cols = [lin_band, base, klo, khi, valid]
    assert len(cols) == META_COLS
    return jnp.stack(cols, axis=1)


# -- fused candidate scoring + top-k -----------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "b", "top_k"))
def score_topk(cand: Array, words: Array, qwords: Array, *, k: int, b: int,
               top_k: int) -> tuple[Array, Array, Array]:
    """(Q, C) -1-padded candidate ids + resident buffers -> ranked partials.

    Returns (ids (Q, top_k) int32 [-1 pad], scores (Q, top_k) float32
    [NEG_INF pad], has_candidates (Q,) bool) — the device image of the
    planner's ``TopKPartial`` rows, in the same (score desc, id asc) order:

    * dedup: sort ids ascending, mask repeats and -1 padding (-1 maps to an
      INT32_MAX sentinel so padding sorts last, not first);
    * score: one row gather from the (N, W) resident packed words, b-bit
      unpack, integer collision counts vs the unpacked query codes —
      invalid columns count -1;
    * rank: two-key ``lax.sort`` on (-count, id): count desc, id asc,
      invalid columns sink.  Identical output to the host planner's stable
      argsort over the ascending candidate union, so fused answers are
      bit-identical; the score is the same ``count.astype(f32) / k``.
    """
    qn, c = cand.shape
    has = jnp.any(cand >= 0, axis=1)
    ids = jnp.where(cand >= 0, cand, _INVALID_ID)
    ids = jax.lax.sort(ids, dimension=1)
    dup = jnp.concatenate(
        [jnp.zeros((qn, 1), bool), ids[:, 1:] == ids[:, :-1]], axis=1)
    valid = (ids != _INVALID_ID) & ~dup
    n = words.shape[0]
    rows = words[jnp.clip(ids, 0, max(n - 1, 0))]          # (Q, C, W)
    ccodes = unpack_codes(rows.reshape(qn * c, -1), k, b).reshape(qn, c, k)
    qcodes = unpack_codes(qwords, k, b)                    # (Q, K)
    counts = jnp.sum(qcodes[:, None, :] == ccodes, axis=-1,
                     dtype=jnp.int32)                      # (Q, C)
    counts = jnp.where(valid, counts, jnp.int32(-1))
    neg, ids = jax.lax.sort((-counts, ids), dimension=1, num_keys=2)
    kk = min(top_k, c)
    out_ids = jnp.full((qn, top_k), -1, jnp.int32)
    out_scores = jnp.full((qn, top_k), -jnp.inf, jnp.float32)
    hit = neg[:, :kk] <= 0                                  # count >= 0
    out_ids = out_ids.at[:, :kk].set(
        jnp.where(hit, ids[:, :kk], jnp.int32(-1)))
    out_scores = out_scores.at[:, :kk].set(
        jnp.where(hit, (-neg[:, :kk]).astype(jnp.float32) / k, -jnp.inf))
    return out_ids, out_scores, has
