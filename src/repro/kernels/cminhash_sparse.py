"""Sparse C-MinHash via contiguous window-mins (the fast signing path).

The gather formulation (``core.cminhash.cminhash_sparse``) computes
``h_q = min_j pi[(idx_j - q - off) mod D]`` with an O(B * nnz * K) random
gather into pi.  Reversing pi turns every hash index into a *contiguous*
window read:

    rev[m]      = pi[(D - 1 - m) mod D]
    s_j         = (D - 1 - idx_j + off) mod D
    h_q         = min_j rev_ext[s_j + q],      q = 0..K-1

where ``rev_ext`` is rev extended circularly by the window length.  Each
nonzero contributes one length-K contiguous slice of a VMEM/cache-resident
table, elementwise-min accumulated — scatter-free, gather-free, exactly the
layout a TPU VPU (and a CPU cache line) wants.  Invalid (padding) entries are
pointed at a SENTINEL region of the table, so no validity masking happens in
the hot loop.

Two implementations of the same scan share the precompute helpers:

* ``cminhash_sparse_windows`` — pure compiled jnp (vmapped dynamic slices);
  the dispatchable fast path on CPU and the oracle-equivalent of the kernel.
* ``cminhash_sparse_pallas`` — the Pallas kernel: grid over (batch tiles,
  nnz tiles), window table resident in VMEM as 128-lane rows, starts in
  SMEM, each window read as one ref load plus one lane rotation and
  min-folded into two lane-masked accumulators, many windows in flight per
  loop trip.  The window length is padded to the 128-lane geometry;
  ``interpret=True`` runs it on CPU.

Both are bit-identical to the gather path (same exact integer mins), and both
take ``pack_b``: the b-bit truncate+pack (``packfmt.pack_codes``) runs inside
the same jit as the scan, so no (B, K) int32 crosses back to the host.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packfmt import pack_codes

Array = jax.Array
SENTINEL = jnp.iinfo(jnp.int32).max


def _check(d: int, k: int) -> None:
    if k > d:
        raise ValueError(f"C-MinHash requires K <= D (got K={k}, D={d})")


def window_table(pi: Array, wl: int, dtype=jnp.int32, sentinel=SENTINEL) -> Array:
    """(D,) pi -> (D + 2*wl - 1,) reversed/extended window table.

    Layout: ``t[m] = pi[(D - 1 - m) mod D]`` for ``m < D + wl - 1`` (circular
    extension so any valid start s < D can read a full wl-window), then wl
    ``sentinel`` entries.  ``invalid_start(d, wl)`` indexes a window that reads
    only sentinel — padding rows/columns resolve to the sentinel with zero
    branching in the scan.  ``sentinel`` must be >= every pi value so it can
    never win a min against real data.
    """
    d = pi.shape[0]
    rev = pi[::-1].astype(dtype)
    reps = -(-(d + wl - 1) // d)
    ext = jnp.tile(rev, reps)[: d + wl - 1]
    return jnp.concatenate([ext, jnp.full((wl,), sentinel, dtype)])


def invalid_start(d: int, wl: int) -> int:
    """Window start whose wl-window lies wholly in the SENTINEL region."""
    return d + wl - 1


def window_starts(idx: Array, d: int, wl: int, *, shift_offset: int) -> Array:
    """(B, NNZ) padded index lists -> (B, NNZ) int32 window starts.

    Valid entries map to ``(D - 1 - idx + off) mod D``; padding (< 0) maps to
    the SENTINEL window start.
    """
    s = (d - 1 - idx + shift_offset) % d
    return jnp.where(idx >= 0, s, invalid_start(d, wl)).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("k", "shift_offset", "block_j", "pack_b"))
def cminhash_sparse_windows(idx: Array, pi: Array, k: int,
                            sigma: Array | None = None, *,
                            shift_offset: int = 1, block_j: int = 64,
                            pack_b: int | None = None) -> Array:
    """Compiled-jnp window-min scan: (B, NNZ) index lists -> (B, K) int32,
    or (B, ceil(K/(32/pack_b))) uint32 packed words when ``pack_b`` is set
    (the b-bit truncate+pack runs inside the same compiled scan).

    Same data movement as the Pallas kernel (contiguous slices of the window
    table, min-folded over nnz tiles of ``block_j``), expressed as vmapped
    ``dynamic_slice`` under ``lax.scan`` so XLA emits block copies instead of
    elementwise gathers.  This is the dispatchable fast path on CPU.

    Two details carry the speedup (profiled on CPU):

    * the per-tile fold is a *halving tree* of elementwise ``minimum`` over
      contiguous (B, jt/2, K) halves — ``jnp.min(axis=1)`` reduces along a
      stride-K axis and is several times slower than the whole gather;
    * when D <= 2^16 every pi value fits uint16, halving the table and fold
      traffic.  The uint16 sentinel (0xFFFF) is the max representable value,
      so it can never beat a real min — only rows with no valid index at all
      need the explicit SENTINEL fixup at the end.

    Results are bit-identical to the gather path in all cases.
    """
    d = pi.shape[0]
    _check(d, k)
    if sigma is not None:
        from ..core.permutations import apply_permutation_sparse
        idx = apply_permutation_sparse(idx, sigma)
    b, nnz = idx.shape
    narrow = d <= (1 << 16)
    dtype, sentinel = ((jnp.uint16, (1 << 16) - 1) if narrow
                       else (jnp.int32, SENTINEL))
    table = window_table(pi, k, dtype, sentinel)
    s = window_starts(idx, d, k, shift_offset=shift_offset)

    # power-of-two tile so the halving tree stays exact halves
    jt = 1 << max(0, min(block_j, nnz).bit_length() - 1)
    nj = -(-nnz // jt)
    if nj * jt != nnz:
        s = jnp.pad(s, ((0, 0), (0, nj * jt - nnz)),
                    constant_values=invalid_start(d, k))

    slice_one = lambda st: jax.lax.dynamic_slice(table, (st,), (k,))
    windows = jax.vmap(jax.vmap(slice_one))          # (B, jt) starts -> (B, jt, K)

    def step(acc, s_tile):                           # s_tile: (B, jt)
        w = windows(s_tile)
        while w.shape[1] > 1:                        # contiguous SIMD halves
            half = w.shape[1] // 2
            w = jnp.minimum(w[:, :half], w[:, half:])
        return jnp.minimum(acc, w[:, 0]), None

    acc0 = jnp.full((b, k), sentinel, dtype)
    s_tiles = s.reshape(b, nj, jt).transpose(1, 0, 2)
    acc, _ = jax.lax.scan(step, acc0, s_tiles)
    out = acc.astype(jnp.int32)
    if narrow:                    # empty rows: uint16 sentinel -> int32 one
        out = jnp.where((idx >= 0).any(axis=1)[:, None], out, SENTINEL)
    return out if pack_b is None else pack_codes(out, pack_b)


_LANES = 128
# window slabs in flight per loop trip, counted in (8, 128) vregs: on a v5e
# 16 one-vreg slabs ran 1.6x faster than 8, and 32 spill the accumulators
_IN_FLIGHT = 16


def loop_widths(bt: int, jt: int, nr: int) -> tuple[int, int]:
    """(documents, nnz steps) the scan loop folds per trip, from the tile.

    One trip keeps ``_IN_FLIGHT`` vregs of window slabs in flight: up to 8
    documents of the tile side by side, the rest by unrolling the nnz loop
    (a divisor of ``jt``).  A slab of nr + 1 lane rows fills
    ceil((nr + 1) / 8) vregs, so taller windows keep fewer in flight."""
    slabs = max(1, _IN_FLIGHT // -(-(nr + 1) // 8))
    group = min(bt, 8, slabs)
    return group, math.gcd(max(1, slabs // group), jt)


def _kernel(table_ref, s_ref, out_ref, *, bt: int, jt: int, nr: int):
    """One (batch tile, nnz tile) step.  The table is (T, 128) lane rows;
    the window at flat start s = 128*r + c is rows r..r+nr rotated left by
    c lanes, each lane taking row i or row i+1 by whether it wrapped.

    Each window is one load of the nr + 1 rows r..r+nr and one rotation.
    Lane l of rotated row i belongs to output row i when l < 128 - c (it did
    not wrap) and to output row i - 1 otherwise, so the slab is min-folded
    into two accumulators by that lane mask, ``lo`` (row i) and ``hi`` (row
    i - 1); output row i is min(lo[i], hi[i + 1]) once the tile's windows
    are in.  Each loop trip folds ``group`` documents times ``unroll`` nnz
    steps (``loop_widths``): that many independent load-roll-min chains are
    in flight, where one window a step left each to wait out the last."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, SENTINEL, out_ref.dtype)

    group, unroll = loop_widths(bt, jt, nr)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nr + 1, _LANES), 1)
    top = jnp.full((nr + 1, _LANES), SENTINEL, jnp.int32)
    for g0 in range(0, bt, group):                    # static doc groups
        docs = range(g0, min(g0 + group, bt))

        def body(i, accs, docs=docs):
            for u in range(unroll):
                jl = i * unroll + u
                out = []
                for bl, (lo, hi) in zip(docs, accs):
                    # s >= 0: row and lane by shift and mask (a signed //
                    # lowers to some 20 scalar ops per window)
                    s = s_ref[bl, jl]
                    c = s & (_LANES - 1)
                    w = pltpu.roll(table_ref[pl.ds(s >> 7, nr + 1), :],
                                   (_LANES - c) & (_LANES - 1), 1)
                    keep = lane < _LANES - c
                    out.append((jnp.minimum(lo, jnp.where(keep, w, top)),
                                jnp.minimum(hi, jnp.where(keep, top, w))))
                accs = tuple(out)
            return accs

        accs = jax.lax.fori_loop(0, jt // unroll, body,
                                 tuple((top, top) for _ in docs))
        for bl, (lo, hi) in zip(docs, accs):
            out_ref[bl] = jnp.minimum(out_ref[bl],
                                      jnp.minimum(lo[:nr], hi[1:]))


@functools.partial(
    jax.jit,
    static_argnames=("k", "shift_offset", "block_b", "block_j", "interpret",
                     "pack_b"),
)
def cminhash_sparse_pallas(idx: Array, pi: Array, k: int, *,
                           shift_offset: int = 1, block_b: int = 8,
                           block_j: int = 128, interpret: bool = True,
                           pack_b: int | None = None) -> Array:
    """Sparse C-MinHash signatures via the tiled Pallas window-min kernel.

    idx: (B, NNZ) padded index lists (entries < 0 are padding), already
    sigma-permuted by the caller; pi: (D,) int32.  Returns (B, K) int32, or
    (B, ceil(K/(32/pack_b))) uint32 packed words when ``pack_b`` is set
    (``pack_codes`` on the kernel's mins, inside the same jit).

    Tiling: grid (batch tiles, nnz tiles).  The window table is one
    VMEM-resident (T, 128) block (D + 2*Kp words — ~0.5 MB at D = 65536,
    K = 1024) and the window starts ride in SMEM, so the only HBM traffic
    per tile is the (Bt, Jt) start block and the (Bt, Kp/128, 128) output
    min-accumulation.  A window is read with one sublane-offset ref load
    and one lane rotation — no value-level dynamic slice, which Mosaic does
    not lower — and the loop folds ``loop_widths(block_b, block_j, Kp/128)``
    windows per trip.  Window length Kp is K padded to the 128-lane
    geometry.
    """
    if shift_offset not in (0, 1):
        raise ValueError("shift_offset must be 0 or 1")
    d = pi.shape[0]
    _check(d, k)
    b, nnz = idx.shape
    bt = max(1, block_b)
    jt = max(1, block_j)
    wl = -(-k // _LANES) * _LANES                     # lane-padded window
    nr = wl // _LANES
    nb, nj = -(-b // bt), -(-nnz // jt)

    # one spare lane row past the last window so row r + nr always exists
    table = window_table(pi, wl)
    rows = -(-table.shape[0] // _LANES) + 1
    table = jnp.pad(table, (0, rows * _LANES - table.shape[0]),
                    constant_values=SENTINEL).reshape(rows, _LANES)

    s0 = invalid_start(d, wl)
    s = jnp.full((nb * bt, nj * jt), s0, jnp.int32)
    s = s.at[:b, :nnz].set(window_starts(idx, d, wl,
                                         shift_offset=shift_offset))

    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, jt=jt, nr=nr),
        grid=(nb, nj),
        in_specs=[
            pl.BlockSpec((rows, _LANES), lambda i, j: (0, 0)),
            pl.BlockSpec((bt, jt), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bt, nr, _LANES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bt, nr, _LANES), jnp.int32),
        interpret=interpret,
    )(table, s)
    sig = out.reshape(nb * bt, wl)[:b, :k]
    return sig if pack_b is None else pack_codes(sig, pack_b)
