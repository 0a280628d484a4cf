"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.permutations import make_two_permutations
from repro.kernels import ops, ref
from repro.kernels.cminhash_kernel import cminhash_pallas
from repro.kernels.collision_kernel import collision_count_pallas


@pytest.mark.parametrize("B,D,K", [
    (1, 64, 1), (2, 64, 64), (4, 100, 37), (8, 256, 256), (3, 777, 300),
    (5, 1024, 1024), (2, 2048, 500),
])
@pytest.mark.parametrize("dens", [0.02, 0.3, 0.9])
def test_cminhash_kernel_matches_ref(B, D, K, dens):
    rng = np.random.default_rng(B * D + K)
    v = (rng.random((B, D)) < dens).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(0), D)
    got = cminhash_pallas(jnp.asarray(v), pi, K, interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32, jnp.bool_])
def test_cminhash_kernel_dtypes(dtype):
    rng = np.random.default_rng(1)
    v = (rng.random((4, 128)) < 0.3)
    _, pi = make_two_permutations(jax.random.PRNGKey(0), 128)
    got = cminhash_pallas(jnp.asarray(v).astype(dtype), pi, 32, interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v.astype(np.int8)), pi, 32)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_b,block_d", [(1, 128), (8, 256), (4, 512)])
def test_cminhash_kernel_block_sizes(block_b, block_d):
    rng = np.random.default_rng(2)
    v = (rng.random((6, 700)) < 0.1).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(3), 700)
    got = cminhash_pallas(jnp.asarray(v), pi, 200, block_b=block_b,
                          block_d=block_d, interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, 200)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_cminhash_kernel_shift_offset_zero():
    rng = np.random.default_rng(4)
    v = (rng.random((2, 96)) < 0.25).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(5), 96)
    got = cminhash_pallas(jnp.asarray(v), pi, 96, shift_offset=0,
                          interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, 96, shift_offset=0)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(16, 400), st.data())
def test_cminhash_kernel_property(B, D, data):
    K = data.draw(st.integers(1, D))
    seed = data.draw(st.integers(0, 2**16))
    dens = data.draw(st.floats(0.0, 1.0))
    rng = np.random.default_rng(seed)
    v = (rng.random((B, D)) < dens).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(seed), D)
    got = cminhash_pallas(jnp.asarray(v), pi, K, interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("Q,N,K", [(1, 1, 1), (64, 64, 128), (37, 53, 130),
                                   (128, 200, 64), (5, 300, 1024)])
def test_collision_kernel_matches_ref(Q, N, K):
    rng = np.random.default_rng(Q + N + K)
    sq = rng.integers(0, 37, (Q, K)).astype(np.int32)
    sn = rng.integers(0, 37, (N, K)).astype(np.int32)
    got = collision_count_pallas(jnp.asarray(sq), jnp.asarray(sn),
                                 interpret=True)
    want = ref.collision_count_ref(jnp.asarray(sq), jnp.asarray(sn))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40), st.integers(1, 200),
       st.integers(0, 2**16))
def test_collision_kernel_property(Q, N, K, seed):
    rng = np.random.default_rng(seed)
    sq = rng.integers(0, 11, (Q, K)).astype(np.int32)
    sn = rng.integers(0, 11, (N, K)).astype(np.int32)
    got = collision_count_pallas(jnp.asarray(sq), jnp.asarray(sn),
                                 interpret=True)
    want = ref.collision_count_ref(jnp.asarray(sq), jnp.asarray(sn))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_ops_wrappers_roundtrip():
    rng = np.random.default_rng(9)
    B, D, K = 6, 512, 128
    v = (rng.random((B, D)) < 0.15).astype(np.int8)
    sigma, pi = make_two_permutations(jax.random.PRNGKey(7), D)
    s_k = ops.cminhash_signatures(jnp.asarray(v), pi, K, sigma,
                                  use_kernel=True)
    s_r = ops.cminhash_signatures(jnp.asarray(v), pi, K, sigma,
                                  use_kernel=False)
    assert np.array_equal(np.asarray(s_k), np.asarray(s_r))
    est = ops.estimated_jaccard_matrix(s_k, s_k)
    assert np.allclose(np.diag(np.asarray(est)), 1.0)


@pytest.mark.parametrize("B,D,K,dens,bd", [
    (2, 64, 64, 0.3, 64), (4, 256, 256, 0.1, 256), (3, 777, 300, 0.5, 64),
    (1, 300, 7, 0.05, 256), (2, 96, 96, 0.9, 64),
])
def test_packed_kernel_matches_ref(B, D, K, dens, bd):
    from repro.kernels.cminhash_packed import cminhash_packed_pallas
    rng = np.random.default_rng(B * D + K)
    v = (rng.random((B, D)) < dens).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(0), D)
    got = cminhash_packed_pallas(jnp.asarray(v), pi, K, block_d=bd,
                                 interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_pack_bits_layout():
    from repro.kernels.cminhash_packed import pack_bits
    rng = np.random.default_rng(5)
    v = (rng.random((2, 70)) < 0.5).astype(np.int8)
    w = np.asarray(pack_bits(jnp.asarray(v)))
    for b in range(2):
        for pos in range(70):
            assert ((w[b, pos // 32] >> (pos % 32)) & 1) == v[b, pos]


@pytest.mark.parametrize("B,D", [(2, 70), (3, 64), (1, 257), (4, 32)])
def test_pack_bits_matches_shift_sum_formulation(B, D):
    # regression for the OR-fold rewrite: identical to the original
    # shift + jnp.sum reduction (which materialized a (B, nw, 32) intermediate)
    from repro.kernels.cminhash_packed import pack_bits
    rng = np.random.default_rng(B * D)
    v = (rng.random((B, D)) < 0.5).astype(np.int8)
    got = np.asarray(pack_bits(jnp.asarray(v)))
    nw = -(-D // 32)
    bits = np.pad((v > 0).astype(np.uint64),
                  ((0, 0), (0, nw * 32 - D))).reshape(B, nw, 32)
    want = np.sum(bits << np.arange(32, dtype=np.uint64),
                  axis=-1).astype(np.uint32)
    assert np.array_equal(got, want)


def _sparse_from_dense(v):
    nnz = max(1, int(v.sum(axis=1).max()))
    idx = np.full((v.shape[0], nnz), -1, np.int32)
    for i in range(v.shape[0]):
        z = np.where(v[i])[0]
        idx[i, : len(z)] = z
    return jnp.asarray(idx)


def _case(B, D, K, dens, block_b=2, block_j=8, pack_b=None, empty_row=False):
    tile = (block_b, block_j, pack_b, empty_row)
    extra = "" if tile == (2, 8, None, False) else (
        f"-b{block_b}j{block_j}" + (f"-pack{pack_b}" if pack_b else "")
        + ("-empty" if empty_row else ""))
    return pytest.param(B, D, K, dens, tile, id=f"{B}-{D}-{K}-{dens}{extra}")


@pytest.mark.parametrize("B,D,K,dens,tile", [
    _case(1, 64, 1, 0.1), _case(2, 64, 64, 0.3), _case(4, 100, 37, 0.05),
    _case(3, 777, 300, 0.02), _case(2, 300, 7, 0.05), _case(2, 96, 96, 0.9),
    # the served tile (block_b 8, block_j 128): a partial batch tile
    # (B = 13), nnz not a multiple of 128, K over 1, 2 and 3 lane rows,
    # packed outputs, a row that is all padding
    _case(13, 777, 128, 0.2, 8, 128),
    _case(13, 777, 256, 0.35, 8, 128),
    _case(13, 777, 300, 0.2, 8, 128, empty_row=True),
    _case(13, 777, 128, 0.35, 8, 128, pack_b=32, empty_row=True),
    _case(13, 777, 256, 0.2, 8, 128, pack_b=8),
    _case(5, 2048, 128, 0.1, 8, 128, pack_b=8, empty_row=True),
    # the loop's other widths (documents x nnz steps a trip): 4 x 4, 1 x 16,
    # two groups of 8 x 2 in a 16-row tile, and 8 x 1 for 9-row slabs
    _case(6, 300, 128, 0.3, 4, 128),
    _case(3, 300, 64, 0.3, 1, 128, empty_row=True),
    _case(13, 777, 128, 0.2, 16, 128),
    _case(3, 2048, 1024, 0.05, 8, 128),
])
@pytest.mark.parametrize("off", [0, 1])
def test_sparse_pallas_kernel_matches_ref(B, D, K, dens, tile, off):
    from repro.kernels.cminhash_sparse import cminhash_sparse_pallas
    from repro.kernels.packfmt import pack_codes
    block_b, block_j, pack_b, empty_row = tile
    rng = np.random.default_rng(B * D + K)
    v = (rng.random((B, D)) < dens).astype(np.int8)
    if empty_row:
        v[B // 2] = 0
    _, pi = make_two_permutations(jax.random.PRNGKey(0), D)
    got = cminhash_sparse_pallas(_sparse_from_dense(v), pi, K,
                                 shift_offset=off, block_b=block_b,
                                 block_j=block_j, interpret=True,
                                 pack_b=pack_b)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K, shift_offset=off)
    if pack_b is not None:
        want = pack_codes(want, pack_b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bt,jt,nr,widths", [
    (8, 128, 1, (8, 2)), (8, 128, 3, (8, 2)), (4, 128, 2, (4, 4)),
    (2, 8, 1, (2, 8)), (1, 128, 1, (1, 16)), (16, 128, 1, (8, 2)),
    (8, 128, 8, (8, 1)), (8, 7, 1, (8, 1)),
])
def test_sparse_loop_widths(bt, jt, nr, widths):
    # 16 vregs of window slabs a trip, documents first, nnz steps dividing jt
    from repro.kernels.cminhash_sparse import loop_widths
    assert loop_widths(bt, jt, nr) == widths


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(16, 300), st.data())
def test_sparse_windows_property(B, D, data):
    from repro.kernels.cminhash_sparse import cminhash_sparse_windows
    K = data.draw(st.integers(1, D))
    seed = data.draw(st.integers(0, 2**16))
    dens = data.draw(st.floats(0.0, 1.0))
    rng = np.random.default_rng(seed)
    v = (rng.random((B, D)) < dens).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(seed), D)
    got = cminhash_sparse_windows(_sparse_from_dense(v), pi, K,
                                  block_j=data.draw(st.integers(1, 8)))
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(32, 300), st.data())
def test_packed_kernel_property(B, D, data):
    from repro.kernels.cminhash_packed import cminhash_packed_pallas
    K = data.draw(st.integers(1, D))
    seed = data.draw(st.integers(0, 2**16))
    rng = np.random.default_rng(seed)
    v = (rng.random((B, D)) < data.draw(st.floats(0.0, 1.0))).astype(np.int8)
    _, pi = make_two_permutations(jax.random.PRNGKey(seed), D)
    got = cminhash_packed_pallas(jnp.asarray(v), pi, K, block_d=64,
                                 interpret=True)
    want = ref.cminhash_dense_ref(jnp.asarray(v), pi, K)
    assert np.array_equal(np.asarray(got), np.asarray(want))
