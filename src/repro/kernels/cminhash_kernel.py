"""Pallas TPU kernel for the C-MinHash circulant min-reduce (the hashing hot loop).

TPU-native formulation (DESIGN.md §4): hash q is a masked min of the fixed value
vector ``pi`` against a circulantly rolled window of the (sigma-permuted) bit
vector:

    h_q = min_m { pi[m] : vpad[m + q + off] != 0 },    vpad = [v, v[:K+off], 0...]

Tiling: with ``Kt == Dt``, the window needed by hash-block ``j`` and data-block
``d`` lies entirely inside the two adjacent Dt-blocks ``d+j`` and ``d+j+1`` of the
flat padded vector — so the kernel consumes two *disjoint* BlockSpecs (no
overlapping windows, no gathers, no mod arithmetic on the data path).  The inner
loop is a VPU select+min over a VMEM band; the output block is min-accumulated
across the innermost grid dimension.

VMEM working set per program instance (defaults Bt=8, Dt=Kt=256):
  band 2*Bt*Dt int8 + pi Dt int32 + acc Bt*Kt int32 ≈ 13 KB  — far under budget;
larger Dt (512/1024) trades grid steps for VMEM.  On TPU the blocks must tile
(8, 128): Bt % 8 == 0 and Dt % 128 == 0.  The window at offset q is a lane
rotation of the band and hash q lands in its column by an iota select —
Mosaic lowers neither a value-level dynamic slice nor a dynamic-column
update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packfmt import pack_codes

Array = jax.Array
SENTINEL = jnp.iinfo(jnp.int32).max


def _kernel(pi_ref, vlo_ref, vhi_ref, out_ref, *, bt: int, dt: int,
            off: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, SENTINEL, out_ref.dtype)

    # (Bt, 2*Dt) band as int32 lanes: Mosaic rotates 32-bit lanes only
    band = jnp.concatenate([vlo_ref[...], vhi_ref[...]],
                           axis=1).astype(jnp.int32)
    pvals = pi_ref[...]                                    # (1, Dt) int32
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, dt), 1)

    def body(k_local, acc):
        # window = band[:, k_local + off :][:, :Dt] as a lane rotation (no
        # value-level dynamic slice, which Mosaic does not lower)
        shift = (2 * dt - (k_local + off)) % (2 * dt)
        window = pltpu.roll(band, shift, 1)[:, :dt]
        masked = jnp.where(window > 0, pvals, SENTINEL)
        hk = jnp.min(masked, axis=1, keepdims=True)        # (Bt, 1)
        return jnp.where(col == k_local, jnp.minimum(acc, hk), acc)

    out_ref[...] = jax.lax.fori_loop(0, dt, body, out_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "shift_offset", "block_b", "block_d", "interpret",
                     "pack_b"),
)
def cminhash_pallas(v: Array, pi: Array, k: int, *, shift_offset: int = 1,
                    block_b: int = 8, block_d: int = 256,
                    interpret: bool = True,
                    pack_b: int | None = None) -> Array:
    """Dense C-MinHash signatures via the tiled Pallas kernel.

    v: (B, D) int8/bool/int32 binary data (already sigma-permuted by the caller);
    pi: (D,) int32 permutation values. Returns (B, K) int32 with column q holding
    the paper's h_{q+shift_offset} — unless ``pack_b`` is set, in which case
    the (B, ceil(K / (32/pack_b))) uint32 packed words come back instead
    (``packfmt.pack_codes`` on the kernel's mins, inside the same jit).
    """
    if shift_offset not in (0, 1):
        raise ValueError("shift_offset must be 0 or 1 (band fits 2 blocks)")
    b, d = v.shape
    if k > d:
        raise ValueError(f"K <= D required (K={k}, D={d})")
    bt, dt = block_b, block_d
    kt = dt  # tiling invariant: hash blocks are the size of data blocks

    nb = -(-b // bt)
    nd = -(-d // dt)
    nk = -(-k // kt)

    # Value vector padded with SENTINEL so out-of-range m never wins the min.
    pi_pad = jnp.full((nd * dt,), SENTINEL, jnp.int32).at[:d].set(pi.astype(jnp.int32))

    # Flat circular buffer: [v, v[:, :K+off], zeros...] then block-pad.
    mask = (v > 0).astype(jnp.int8)
    n_vblocks = nd + nk  # max block index used is (nd-1) + (nk-1) + 1
    vpad = jnp.zeros((nb * bt, n_vblocks * dt), jnp.int8)
    vpad = vpad.at[:b, :d].set(mask)
    # Real reads touch flat positions up to (d-1) + (K-1+off): a wrap copy of
    # length K+off-1 suffices; clamp to D (single wrap; K <= D) and to the
    # allocated width (the clipped tail is only ever read for padded hash
    # columns, which are sliced off below).
    wrap = min(k + shift_offset, d, n_vblocks * dt - d)
    vpad = vpad.at[:b, d:d + wrap].set(mask[:, :wrap])

    grid = (nb, nk, nd)
    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, dt=dt, off=shift_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, dt), lambda i, j, dd: (0, dd)),
            pl.BlockSpec((bt, dt), lambda i, j, dd: (i, dd + j)),
            pl.BlockSpec((bt, dt), lambda i, j, dd: (i, dd + j + 1)),
        ],
        out_specs=pl.BlockSpec((bt, kt), lambda i, j, dd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nb * bt, nk * kt), jnp.int32),
        interpret=interpret,
    )(pi_pad.reshape(1, -1), vpad, vpad)
    sig = out[:b, :k]
    return sig if pack_b is None else pack_codes(sig, pack_b)
