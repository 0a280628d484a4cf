"""Public jit'd wrappers around the Pallas kernels with reference fallbacks.

Signing requests route through ``kernels.dispatch`` (shape/backend kernel
selection + autotuned block sizes); pairwise scoring wraps the collision
kernel directly.  The wrappers keep signature semantics identical across
paths so callers (engine, dedup pipeline, benchmarks) can switch freely;
tests sweep shapes/dtypes asserting kernel == ref.

The b-bit packed-code format lives in ``kernels.packfmt``; its geometry,
``pack_codes`` and ``unpack_codes`` are re-exported here for the store/planner.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import metrics as obs_metrics
from . import dispatch, ref
from .collision_kernel import collision_count_pallas
from .packfmt import (PACK_BITS, pack_codes,  # noqa: F401  (re-exports)
                      pack_geometry, unpack_codes)

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def cminhash_signatures(v: Array, pi: Array, k: int, sigma: Array | None = None,
                        *, shift_offset: int = 1, use_kernel: bool = True,
                        block_b: int | None = None, block_d: int | None = None,
                        impl: str = "auto") -> Array:
    """Dense C-MinHash signatures (B, D) -> (B, K) via the dispatch layer.

    ``use_kernel=True`` lets dispatch pick the kernel by shape/backend (pass
    ``impl`` to force one); ``use_kernel=False`` is the jnp oracle.  Blocks
    left as None come from the autotune cache.
    """
    if use_kernel and impl == "auto" and (block_b, block_d) != (None, None):
        impl = "int8"   # explicit block request pins the historical kernel
    return dispatch.signatures_dense(
        v, pi, k, sigma, shift_offset=shift_offset, use_kernel=use_kernel,
        impl=impl, block_b=block_b, block_d=block_d)


def cminhash_signatures_packed(v: Array, pi: Array, k: int, b: int,
                               sigma: Array | None = None, *,
                               shift_offset: int = 1, use_kernel: bool = True,
                               impl: str = "auto") -> Array:
    """Fused sign->pack: (B, D) binary -> (B, ceil(K/(32/b))) uint32 words,
    bit-identical to ``pack_codes(cminhash_signatures(...), b)``."""
    return dispatch.signatures_dense(
        v, pi, k, sigma, shift_offset=shift_offset, use_kernel=use_kernel,
        impl=impl, pack_b=b)


def collision_counts(sig_q: Array, sig_n: Array, *, use_kernel: bool = True,
                     block_q: int = 64, block_n: int = 128,
                     block_k: int = 128) -> Array:
    """(Q, K) x (N, K) -> (Q, N) int32 match counts via kernel or oracle.
    The default blocks tile the TPU's (8, 128) layout."""
    obs_metrics.default().counter(
        f"kernel.collision.{'pallas' if use_kernel else 'ref'}").inc()
    if use_kernel:
        return collision_count_pallas(sig_q, sig_n, block_q=block_q,
                                      block_n=block_n, block_k=block_k,
                                      interpret=_interpret())
    return ref.collision_count_ref(sig_q, sig_n)


def estimated_jaccard_matrix(sig_q: Array, sig_n: Array, **kw) -> Array:
    """(Q, N) float32 estimated Jaccard from signatures."""
    k = sig_q.shape[-1]
    return collision_counts(sig_q, sig_n, **kw).astype(jnp.float32) / k


# -- b-bit packed-code scoring (SketchStore storage format) ------------------
# (format + pack/unpack live in kernels.packfmt; re-exported above)

def packed_collision_counts(words_q: Array, words_n: Array, k: int, b: int,
                            *, unpack_block_n: int = 16384, **kw) -> Array:
    """(Q, W) x (N, W) packed uint32 -> (Q, N) int32 matching-code counts.

    Unpacks and reuses the pairwise collision kernel.  The index side is
    processed in blocks of ``unpack_block_n`` rows so the unpacked (N', K)
    int32 intermediate stays bounded — the resident index keeps its b/32
    packed footprint even when a brute-force fallback scores all of it.
    """
    uq = unpack_codes(words_q, k, b)
    n = words_n.shape[0]
    if n <= unpack_block_n:
        return collision_counts(uq, unpack_codes(words_n, k, b), **kw)
    parts = [collision_counts(
        uq, unpack_codes(words_n[lo: lo + unpack_block_n], k, b), **kw)
        for lo in range(0, n, unpack_block_n)]
    return jnp.concatenate(parts, axis=1)


def packed_estimated_jaccard_matrix(words_q: Array, words_n: Array, k: int,
                                    b: int, **kw) -> Array:
    """(Q, N) float32 estimated Jaccard from b-bit packed codes.

    At b < 32 this is the raw collision fraction of b-bit codes — biased up by
    ~2^-b relative to true Jaccard (Li & Koenig, 2011); at b = 32 it equals
    ``estimated_jaccard_matrix`` exactly.
    """
    counts = packed_collision_counts(words_q, words_n, k, b, **kw)
    return counts.astype(jnp.float32) / k
