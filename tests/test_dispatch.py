"""Dispatch-layer parity sweeps: every signing path == the jnp oracle.

Covers the non-divisible shapes the tiling has to get right — b % block_b,
d % block_d, k < block_d, k % 32 — for shift_offset in {0, 1}, plus the fused
sign->pack output (bit-identical to sign-then-pack_codes for every b), the
engine's config routing, and the packed store ingest path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cminhash
from repro.core.engine import SketchConfig, SketchEngine
from repro.core.permutations import make_two_permutations
from repro.kernels import dispatch, ops, ref
from repro.kernels.packfmt import PACK_BITS, pack_codes

# b % block_b != 0, d % block_d != 0, k < block_d, k % 32 != 0 all appear
SHAPES = [
    (3, 100, 37, 0.05),    # k % 32 != 0, d % block_d != 0, b % block_b != 0
    (5, 300, 300, 0.3),    # k > block_d after clamping? k % 32 != 0
    (2, 257, 129, 0.9),    # everything prime-ish
    (4, 96, 7, 0.1),       # k < block_d, tiny k
    (1, 64, 64, 0.5),      # exact fit
]
BLOCKS = {"block_b": 4, "block_d": 64}


def _inputs(b, d, dens, seed):
    rng = np.random.default_rng(seed)
    v = (rng.random((b, d)) < dens).astype(np.int8)
    nnz = max(1, int(v.sum(axis=1).max()))
    idx = np.full((b, nnz), -1, np.int32)
    for i in range(b):
        z = np.where(v[i])[0]
        idx[i, : len(z)] = z
    _, pi = make_two_permutations(jax.random.PRNGKey(seed), d)
    return jnp.asarray(v), jnp.asarray(idx), pi


@pytest.mark.parametrize("B,D,K,dens", SHAPES)
@pytest.mark.parametrize("off", [0, 1])
def test_dense_impls_match_ref(B, D, K, dens, off):
    v, _, pi = _inputs(B, D, dens, B * D + K + off)
    want = np.asarray(ref.cminhash_dense_ref(v, pi, K, shift_offset=off))
    for impl in ("int8", "packed", "ref"):
        got = dispatch.signatures_dense(v, pi, K, shift_offset=off,
                                        impl=impl, **BLOCKS)
        assert np.array_equal(np.asarray(got), want), impl


@pytest.mark.parametrize("B,D,K,dens", SHAPES)
@pytest.mark.parametrize("off", [0, 1])
def test_sparse_impls_match_ref(B, D, K, dens, off):
    v, idx, pi = _inputs(B, D, dens, B * D + K + off)
    want = np.asarray(ref.cminhash_dense_ref(v, pi, K, shift_offset=off))
    for impl, blocks in (("gather", {}),
                         ("windows", {"block_j": 4}),
                         ("pallas", {"block_b": 4, "block_j": 4})):
        got = dispatch.signatures_sparse(idx, pi, K, shift_offset=off,
                                         impl=impl, **blocks)
        assert np.array_equal(np.asarray(got), want), impl


def test_sparse_all_padding_rows():
    # rows with zero valid indices must sign to SENTINEL on every path
    _, pi = make_two_permutations(jax.random.PRNGKey(0), 128)
    idx = jnp.asarray(np.array([[-1, -1, -1], [3, -1, -1]], np.int32))
    want = np.asarray(dispatch.signatures_sparse(idx, pi, 32, impl="gather"))
    assert (want[0] == np.iinfo(np.int32).max).all()
    for impl in ("windows", "pallas"):
        got = dispatch.signatures_sparse(idx, pi, 32, impl=impl)
        assert np.array_equal(np.asarray(got), want), impl


def test_sparse_with_sigma_matches_dense():
    v, idx, pi = _inputs(4, 200, 0.1, 11)
    sigma, _ = make_two_permutations(jax.random.PRNGKey(3), 200)
    want = np.asarray(dispatch.signatures_dense(v, pi, 64, sigma, impl="ref"))
    for impl in ("gather", "windows", "pallas"):
        got = dispatch.signatures_sparse(idx, pi, 64, sigma, impl=impl)
        assert np.array_equal(np.asarray(got), want), impl


@pytest.mark.parametrize("B,D,K,dens", [(3, 100, 37, 0.05), (2, 257, 129, 0.3),
                                        (4, 96, 7, 0.1)])
@pytest.mark.parametrize("b", PACK_BITS)
def test_fused_pack_bit_identical(B, D, K, dens, b):
    v, idx, pi = _inputs(B, D, dens, B + D + K)
    sig = ref.cminhash_dense_ref(v, pi, K)
    want = np.asarray(pack_codes(sig, b))
    for impl in ("int8", "packed", "ref"):
        got = dispatch.signatures_dense(v, pi, K, impl=impl, pack_b=b,
                                        **BLOCKS)
        assert got.dtype == jnp.uint32
        assert np.array_equal(np.asarray(got), want), impl
    # sparse paths: every impl packs inside its jit and must agree
    # bit-for-bit
    for impl, blocks in (("gather", {}),
                         ("windows", {"block_j": 4}),
                         ("pallas", {"block_b": 2, "block_j": 4})):
        got = dispatch.signatures_sparse(idx, pi, K, impl=impl, pack_b=b,
                                         **blocks)
        assert got.dtype == jnp.uint32, impl
        assert np.array_equal(np.asarray(got), want), impl


def test_auto_policy():
    # CPU: compiled jnp twins; TPU: the kernels that lower there — int8 at
    # every D (the packed kernel's blocks do not tile the TPU lanes)
    assert dispatch.select_dense_impl(512, backend="cpu") == "ref"
    assert dispatch.select_dense_impl(512, use_kernel=False,
                                      backend="tpu") == "ref"
    assert dispatch.select_dense_impl(512, backend="tpu") == "int8"
    assert dispatch.select_dense_impl(1 << 16, backend="tpu") == "int8"
    # the probe's records never fit VMEM at served sizes: jnp twin on TPU
    assert dispatch.select_probe_impl(backend="tpu") == "jnp"
    assert dispatch.select_probe_impl(backend="cpu") == "numpy"
    assert dispatch.select_sparse_impl(backend="cpu") == "windows"
    assert dispatch.select_sparse_impl(backend="tpu") == "pallas"
    assert dispatch.select_sparse_impl(use_kernel=False,
                                       backend="tpu") == "gather"
    with pytest.raises(ValueError):
        dispatch.signatures_dense(jnp.zeros((1, 8), jnp.int8),
                                  jnp.arange(8, dtype=jnp.int32), 4,
                                  impl="nope")


def test_engine_sparse_respects_config(monkeypatch):
    """signatures_sparse must route through dispatch with the engine config
    (it used to call cminhash_sparse directly, ignoring use_kernel/blocks)."""
    calls = []
    real = dispatch.signatures_sparse

    def spy(*args, **kw):
        calls.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr("repro.kernels.dispatch.signatures_sparse", spy)
    cfg = SketchConfig(d=256, k=32, use_kernel=False, block_j=4, seed=0)
    eng = SketchEngine(cfg)
    idx = jnp.asarray(np.array([[1, 5, 9, -1]], np.int32))
    sig = eng.signatures_sparse(idx)
    assert calls and calls[-1]["use_kernel"] is False
    assert calls[-1]["block_j"] == 4
    # and the values still match the direct gather formulation
    want = cminhash.cminhash_sparse(idx, eng.pi, 32, eng.sigma)
    assert np.array_equal(np.asarray(sig), np.asarray(want))

    eng2 = SketchEngine(SketchConfig(d=256, k=32, use_kernel=True, seed=0))
    sig2 = eng2.signatures_sparse(idx)
    assert calls[-1]["use_kernel"] is True
    assert np.array_equal(np.asarray(sig2), np.asarray(want))


def test_engine_sign_packed_matches_two_step():
    eng = SketchEngine(SketchConfig(d=512, k=64, seed=2))
    rng = np.random.default_rng(2)
    v = jnp.asarray((rng.random((6, 512)) < 0.1).astype(np.int8))
    sig = eng.signatures_dense(v)
    for b in PACK_BITS:
        got = eng.sign_packed(v, b)
        assert np.array_equal(np.asarray(got),
                              np.asarray(pack_codes(sig, b))), b


def test_ops_wrapper_still_dispatches():
    v, _, pi = _inputs(4, 300, 0.2, 21)
    a = ops.cminhash_signatures(v, pi, 100, use_kernel=True)
    b = ops.cminhash_signatures(v, pi, 100, use_kernel=False)
    c = ops.cminhash_signatures(v, pi, 100, block_b=4, block_d=64)
    w = ops.cminhash_signatures_packed(v, pi, 100, 8)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))
    assert np.array_equal(np.asarray(w), np.asarray(pack_codes(a, 8)))


def test_band_mode_survives_snapshot(tmp_path):
    from repro.store import SketchStore, StoreConfig

    eng = SketchEngine(SketchConfig(d=512, k=64, seed=5))
    rng = np.random.default_rng(5)
    v = jnp.asarray((rng.random((8, 512)) < 0.1).astype(np.int8))
    cfg = StoreConfig(k=64, n_bands=16, rows_per_band=4, b=8, capacity=16)
    s = SketchStore(cfg)
    s.add_packed(np.asarray(eng.sign_packed(v, 8)))
    path = str(tmp_path / "store.npz")
    s.save(path)
    loaded = SketchStore.load(path)
    # the packed pin must survive the round-trip: raw-sig queries on a
    # packed-keyed table would silently miss every candidate
    with pytest.raises(ValueError):
        loaded.query(np.zeros((1, 64), np.int32))
    qi, _ = loaded.query_packed(np.asarray(eng.sign_packed(v[:3], 8)), 2)
    assert (qi[:, 0] >= 0).all()


def test_store_packed_ingest_interop():
    from repro.store import SketchStore, StoreConfig

    eng = SketchEngine(SketchConfig(d=512, k=64, seed=3))
    rng = np.random.default_rng(3)
    v = jnp.asarray((rng.random((24, 512)) < 0.08).astype(np.int8))
    sigs = np.asarray(eng.signatures_dense(v))

    # b=32: packed ingest interoperates exactly with the sig path
    cfg = StoreConfig(k=64, n_bands=16, rows_per_band=4, b=32, capacity=32)
    s_sig, s_pack = SketchStore(cfg), SketchStore(cfg)
    s_sig.add(sigs)
    s_pack.add_packed(np.asarray(eng.sign_packed(v, 32)))
    i1, sc1 = s_sig.query(sigs[:6], top_k=4)
    i2, sc2 = s_pack.query(sigs[:6], top_k=4)
    i3, sc3 = s_pack.query_packed(np.asarray(pack_codes(jnp.asarray(sigs[:6]),
                                                        32)), top_k=4)
    assert np.array_equal(i1, i2) and np.allclose(sc1, sc2)
    assert np.array_equal(i1, i3) and np.allclose(sc1, sc3)

    # b=8: fully-packed store (ingest + query) finds exact duplicates
    cfg8 = StoreConfig(k=64, n_bands=16, rows_per_band=4, b=8, capacity=32)
    s8 = SketchStore(cfg8)
    ids = s8.add_packed(np.asarray(eng.sign_packed(v, 8)))
    qi, qs = s8.query_packed(np.asarray(eng.sign_packed(v[:5], 8)), top_k=3)
    assert np.array_equal(qi[:, 0], ids[:5])
    assert np.allclose(qs[:, 0], 1.0)

    # word-misaligned bands must refuse loudly
    cfg_bad = StoreConfig(k=64, n_bands=32, rows_per_band=2, b=8, capacity=32)
    with pytest.raises(ValueError):
        SketchStore(cfg_bad).add_packed(
            np.asarray(eng.sign_packed(v[:2], 8)))
    # ...including when pad words make W % n_bands == 0 hold by accident
    cfg_sly = StoreConfig(k=10, n_bands=2, rows_per_band=5, b=4, capacity=8)
    with pytest.raises(ValueError):
        SketchStore(cfg_sly).add_packed(np.zeros((1, 2), np.uint32))

    # b < 32: sig-keys and packed keys differ — mixing modes must raise,
    # not silently miss candidates
    s_mix = SketchStore(cfg8)
    s_mix.add(sigs)
    with pytest.raises(ValueError):
        s_mix.add_packed(np.asarray(eng.sign_packed(v[:2], 8)))
    with pytest.raises(ValueError):
        s_mix.query_packed(np.asarray(eng.sign_packed(v[:2], 8)))
    s_mix.query(sigs[:2])              # same-mode queries still fine


def test_buffer_append_packed_matches_append():
    from repro.store.packed import PackedConfig, PackedSignatureBuffer

    rng = np.random.default_rng(4)
    sigs = rng.integers(0, 1 << 20, (10, 48), dtype=np.int32)
    for b in (8, 32):
        b1 = PackedSignatureBuffer(PackedConfig(k=48, b=b, capacity=8))
        b2 = PackedSignatureBuffer(PackedConfig(k=48, b=b, capacity=8))
        b1.append(sigs)
        b2.append_packed(np.asarray(pack_codes(jnp.asarray(sigs), b)))
        assert np.array_equal(b1.all_packed(), b2.all_packed())
    with pytest.raises(ValueError):
        b2.append_packed(np.zeros((2, 3), np.uint32))
