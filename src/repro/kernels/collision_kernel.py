"""Pallas TPU kernel for pairwise signature collision counting (search hot loop).

count[q, n] = sum_k 1{sig_q[q, k] == sig_n[n, k]} — an "equality matmul": the data
flow is exactly a (Q, K) x (K, N) contraction with (==, +) instead of (*, +), so the
same VMEM tiling that feeds the MXU feeds the VPU here.  Estimated Jaccard is
count / K (estimators.pairwise_jaccard_from_signatures is the oracle).

K-padding uses distinct sentinels per side so padded columns can never match.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(q_ref, nt_ref, out_ref, *, kt: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qs = q_ref[...]                    # (Qt, Kt)
    acc = out_ref[...]                 # (Qt, Nt)
    for kk in range(kt):               # static: one (Qt, Nt) compare each
        acc += (qs[:, kk:kk + 1] == nt_ref[kk:kk + 1, :]).astype(jnp.int32)
    out_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_k", "interpret"))
def collision_count_pallas(sig_q: Array, sig_n: Array, *, block_q: int = 64,
                           block_n: int = 128, block_k: int = 128,
                           interpret: bool = True) -> Array:
    """(Q, K) x (N, K) int32 signatures -> (Q, N) int32 match counts.

    The index side is fed transposed, (K, N): each of the Kt static steps
    compares a query column (Qt, 1) with an index row (1, Nt), so the
    accumulator is a lane-dense (Qt, Nt) block and no step reduces across
    lanes.  On TPU the blocks must tile (8, 128): Qt % 8 == 0 and
    Nt % 128 == 0 (Kt is a sublane dim of the index side, % 8 == 0)."""
    q, k = sig_q.shape
    n, k2 = sig_n.shape
    if k != k2:
        raise ValueError(f"signature widths differ: {k} vs {k2}")
    qt, nt, kt = block_q, block_n, block_k
    nq, nn, nk = -(-q // qt), -(-n // nt), -(-k // kt)

    qp = jnp.full((nq * qt, nk * kt), -1, jnp.int32).at[:q, :k].set(sig_q)
    ntp = jnp.full((nk * kt, nn * nt), -2, jnp.int32).at[:k, :n].set(sig_n.T)

    out = pl.pallas_call(
        functools.partial(_kernel, kt=kt),
        grid=(nq, nn, nk),
        in_specs=[
            pl.BlockSpec((qt, kt), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((kt, nt), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((qt, nt), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq * qt, nn * nt), jnp.int32),
        interpret=interpret,
    )(qp, ntp)
    return out[:q, :n]
