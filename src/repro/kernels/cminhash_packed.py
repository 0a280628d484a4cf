"""Bit-packed C-MinHash kernel (beyond-paper §Perf optimization).

The int8 kernel's HBM traffic is dominated by the circulant mask bands:
~2*B*D*(K/Kt) bytes per signature batch. Packing the binary vector into uint32
words (32 positions/word) cuts that operand 8x; the kernel funnel-shifts the
word pair straddling each window offset and unpacks bits in VREGs (VPU work is
cheap next to the HBM stream — see the §Perf napkin math).

Layout: ``vpacked[b, w]`` holds positions ``32w .. 32w+31`` with position
``32w + j`` at bit ``j``. Blocks stay Kt == Dt with Dt % 32 == 0; the band for
(hash-block j, data-block d) is the word range of flat positions
[(d+j)*Dt, (d+j+2)*Dt) — two adjacent word-blocks, as in the int8 kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .packfmt import pack_codes

Array = jax.Array
SENTINEL = jnp.iinfo(jnp.int32).max


def pack_bits(v: Array) -> Array:
    """(B, D) binary -> (B, ceil(D/32)) uint32, position 32w+j at bit j.

    Folded as 32 strided slices OR'd into the word lanes — no (B, nw, 32)
    int32 intermediate (the shift+sum formulation materialized one, 32x the
    output size, before reducing).
    """
    b, d = v.shape
    nw = -(-d // 32)
    pad = nw * 32 - d
    bits = (v > 0).astype(jnp.uint32)
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
    return functools.reduce(
        jnp.bitwise_or,
        [bits[:, j::32] << jnp.uint32(j) for j in range(32)])


def _kernel(pi_ref, wlo_ref, whi_ref, out_ref, *, bt: int, dt: int,
            off: int):
    d_idx = pl.program_id(2)

    @pl.when(d_idx == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, SENTINEL, out_ref.dtype)

    words = jnp.concatenate([wlo_ref[...], whi_ref[...]], axis=1)  # (Bt, 2*Dt/32)
    pvals = pi_ref[...]                                            # (Dt,) int32
    n_win = dt // 32
    bit_ids = jnp.arange(32, dtype=jnp.uint32)

    def body(k_local, acc):
        shift = k_local + off
        w0 = shift // 32
        b_off = (shift % 32).astype(jnp.uint32)
        lo = jax.lax.dynamic_slice(words, (0, w0), (bt, n_win))
        hi = jax.lax.dynamic_slice(words, (0, w0 + 1), (bt, n_win))
        # funnel shift: window word w = lo >> b_off | hi << (32 - b_off)
        win = jnp.where(
            b_off == 0, lo,
            (lo >> b_off) | (hi << ((32 - b_off) % 32)))
        bits = (win[:, :, None] >> bit_ids) & 1                    # (Bt, n_win, 32)
        mask = bits.reshape(bt, dt) > 0
        masked = jnp.where(mask, pvals[None, :], SENTINEL)
        return acc.at[:, k_local].min(jnp.min(masked, axis=1))

    out_ref[...] = jax.lax.fori_loop(0, dt, body, out_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "shift_offset", "block_b", "block_d", "interpret",
                     "pack_b"),
)
def cminhash_packed_pallas(v: Array, pi: Array, k: int, *,
                           shift_offset: int = 1, block_b: int = 8,
                           block_d: int = 256, interpret: bool = True,
                           pack_b: int | None = None) -> Array:
    """Signatures from a dense binary (B, D) via the bit-packed kernel.

    With ``pack_b`` set, returns (B, ceil(K / (32/pack_b))) uint32 packed
    words (``packfmt.pack_codes`` inside the same jit) instead of (B, K)
    int32.

    Interpret mode only for now: its (Bt, Dt/32) word blocks are narrower
    than the 128-lane TPU tiling (aligned blocks would force Dt >= 4096 and
    with it Kt = Dt hash columns per block), and the in-kernel word window
    is a value-level dynamic slice, which Mosaic does not lower.
    ``kernels.dispatch`` never selects it on TPU.
    """
    if shift_offset not in (0, 1):
        raise ValueError("shift_offset must be 0 or 1")
    if block_d % 32:
        raise ValueError("block_d must be a multiple of 32")
    b, d = v.shape
    if k > d:
        raise ValueError(f"K <= D required (K={k}, D={d})")
    bt, dt = block_b, block_d
    kt = dt
    nb, nd, nk = -(-b // bt), -(-d // dt), -(-k // kt)

    pi_pad = jnp.full((nd * dt,), SENTINEL, jnp.int32).at[:d].set(
        pi.astype(jnp.int32))

    mask = (v > 0).astype(jnp.int8)
    n_vblocks = nd + nk
    flat = jnp.zeros((nb * bt, n_vblocks * dt), jnp.int8)
    flat = flat.at[:b, :d].set(mask)
    wrap = min(k + shift_offset, d, n_vblocks * dt - d)
    flat = flat.at[:b, d:d + wrap].set(mask[:, :wrap])
    words = pack_bits(flat)                       # (B', n_vblocks * Dt/32)
    # (the in-kernel hi-slice can only run past the 2-block window when
    # b_off == 0, where its value is unused — dynamic_slice clamps safely)

    wpb = dt // 32  # words per block
    grid = (nb, nk, nd)
    in_specs = [
        pl.BlockSpec((dt,), lambda i, j, dd: (dd,)),
        pl.BlockSpec((bt, wpb), lambda i, j, dd: (i, dd + j)),
        pl.BlockSpec((bt, wpb), lambda i, j, dd: (i, dd + j + 1)),
    ]
    sig_spec = pl.BlockSpec((bt, kt), lambda i, j, dd: (i, j))
    sig_shape = jax.ShapeDtypeStruct((nb * bt, nk * kt), jnp.int32)

    out = pl.pallas_call(
        functools.partial(_kernel, bt=bt, dt=dt, off=shift_offset),
        grid=grid, in_specs=in_specs, out_specs=sig_spec,
        out_shape=sig_shape, interpret=interpret,
    )(pi_pad, words, words)
    sig = out[:b, :k]
    return sig if pack_b is None else pack_codes(sig, pack_b)
