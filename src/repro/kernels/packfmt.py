"""b-bit packed-code format: the one definition of the storage layout.

K codes of b bits each are packed little-endian into ceil(K / (32/b)) uint32
words: code j of a row lives at bit (j % (32/b)) * b of word j // (32/b).
b == 32 is a bitcast (one code per word, codes == signatures), so scoring on
packed words at b = 32 is bit-exact with scoring the raw signatures.

This module is a leaf (jax-only imports): the host-side packers, the
signing kernels' ``pack_b`` option and the scorers all share the one
geometry defined here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array

PACK_BITS = (1, 2, 4, 8, 16, 32)  # b values whose codes tile an int32 word


def pack_geometry(k: int, b: int) -> tuple[int, int]:
    """-> (codes_per_word, n_words) for K b-bit codes."""
    if b not in PACK_BITS:
        raise ValueError(f"b must be one of {PACK_BITS} (got {b})")
    codes_per_word = 32 // b
    return codes_per_word, -(-k // codes_per_word)


@functools.partial(jax.jit, static_argnames=("b",))
def pack_codes(sig: Array, b: int) -> Array:
    """(B, K) int32 signatures -> (B, W) uint32 b-bit packed words."""
    bsz, k = sig.shape
    cpw, n_words = pack_geometry(k, b)
    if b == 32:
        return jax.lax.bitcast_convert_type(sig, jnp.uint32)
    codes = (sig & ((1 << b) - 1)).astype(jnp.uint32)
    pad = n_words * cpw - k
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * b)
    return jnp.sum(codes.reshape(bsz, n_words, cpw) << shifts, axis=-1,
                   dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k", "b"))
def unpack_codes(words: Array, k: int, b: int) -> Array:
    """(B, W) uint32 packed words -> (B, K) int32 codes in [0, 2^b)."""
    bsz = words.shape[0]
    cpw, n_words = pack_geometry(k, b)
    if b == 32:
        return jax.lax.bitcast_convert_type(words, jnp.int32)[:, :k]
    shifts = (jnp.arange(cpw, dtype=jnp.uint32) * b)
    mask = jnp.uint32((1 << b) - 1)
    codes = (words[:, :, None] >> shifts) & mask
    return codes.reshape(bsz, n_words * cpw)[:, :k].astype(jnp.int32)
