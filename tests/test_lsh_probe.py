"""Device-resident LSH probe: jnp twin + Pallas kernel vs the numpy walk.

The parity contract: for any table geometry (including non-divisible slot
counts, odd bucket widths, short probe chains, heavy spill) and any query
batch (present keys, absent keys, sentinel-valued hashes), every probe
backend returns exactly the candidate rows of ``BandedLSHTable.lookup``'s
host loop — element-for-element, since all backends gather the same record
row for a hit.
"""

import numpy as np
import pytest

from repro.core.lsh import band_hashes
from repro.kernels import dispatch, lsh_probe
from repro.store import BandedLSHTable, SketchStore, StoreConfig
from repro.store.table import SENTINEL_KEY

# (n_slots, bucket_width, max_probes, n_bands): primes and non-powers on
# purpose — slot wraps, partial tiles, and truncation must all be exercised
GEOMETRIES = [
    (37, 3, 5, 5),
    (64, 2, 4, 4),
    (101, 7, 16, 8),
    (16, 1, 2, 3),       # tiny: heavy spill, most lookups miss
]


def _loaded_table(ns, w, mp, nb, n=260, seed=2):
    rng = np.random.default_rng(seed)
    sigs = rng.integers(0, 40, (n, nb * 4), dtype=np.int32)  # forced clashes
    hashes = band_hashes(sigs, nb, 4)
    hashes[5, 0] = SENTINEL_KEY          # sentinel-valued hash -> spill
    t = BandedLSHTable(nb, n_slots=ns, bucket_width=w, max_probes=mp)
    t.insert(hashes[: n // 2], np.arange(n // 2))
    t.insert(hashes[n // 2:], np.arange(n // 2, n))
    return t, hashes


@pytest.mark.parametrize("ns,w,mp,nb", GEOMETRIES)
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_probe_parity_vs_numpy_lookup(ns, w, mp, nb, impl):
    t, hashes = _loaded_table(ns, w, mp, nb)
    qh = hashes[:70].copy()
    qh[3, 1] = SENTINEL_KEY              # sentinel query must match nothing
    rng = np.random.default_rng(9)
    qh[60:] = rng.integers(0, 1 << 60, (10, nb)).astype(np.uint64)  # absent
    want = t.lookup(qh)
    got = t.lookup(qh, impl=impl)
    assert got.shape == want.shape
    assert got.dtype == want.dtype
    assert np.array_equal(got, want)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_probe_parity_after_rebuild(impl):
    t, hashes = _loaded_table(32, 2, 3, 4)
    assert t.n_spilled > 0
    t.rebuild(n_slots=257, bucket_width=8, max_probes=16)  # prime slots
    want = t.lookup(hashes[:40])
    got = t.lookup(hashes[:40], impl=impl)
    assert np.array_equal(got, want)


def test_probe_device_cache_invalidates_on_insert():
    """device_records must re-upload after mutation, not serve stale rows."""
    t, hashes = _loaded_table(101, 4, 8, 4, n=60)
    first = t.lookup(hashes[:10], impl="jnp")
    extra = band_hashes(
        np.random.default_rng(3).integers(0, 40, (30, 16), dtype=np.int32),
        4, 4)
    t.insert(extra, np.arange(60, 90))
    assert first.shape == (10, t.n_bands * t.bucket_width)
    want = t.lookup(np.concatenate([hashes[:10], extra[:5]]))
    got = t.lookup(np.concatenate([hashes[:10], extra[:5]]), impl="jnp")
    assert np.array_equal(got, want)       # stale upload would diverge here


@pytest.mark.parametrize("block_e", [1, 7, 64, 1024])
def test_probe_pallas_entry_tiling(block_e):
    """E % block_e != 0 must pad with invalid entries, never wrap."""
    t, hashes = _loaded_table(37, 3, 5, 5, n=90)
    meta = lsh_probe.probe_operands(hashes[:11], t.n_slots)
    import jax.numpy as jnp
    out = lsh_probe.lsh_probe_pallas(
        t.device_records(), jnp.asarray(meta), n_slots=t.n_slots,
        max_probes=t.max_probes, block_e=block_e)
    want = t.lookup(hashes[:11])
    got = np.asarray(out).reshape(11, -1)
    assert np.array_equal(got, want)


def test_probe_dispatch_guards():
    t, hashes = _loaded_table(37, 3, 5, 5, n=40)
    with pytest.raises(ValueError):
        t.lookup(hashes[:2], impl="nope")
    with pytest.raises(ValueError):
        dispatch.lsh_probe(t.device_records(), hashes[:2],
                           n_slots=t.n_slots, max_probes=t.max_probes,
                           impl="numpy")
    # TPU: the jnp twin — the Pallas kernel's VMEM-resident records cannot
    # hold a served-size table
    assert dispatch.select_probe_impl(backend="tpu") == "jnp"
    assert dispatch.select_probe_impl(backend="cpu") == "numpy"


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_store_query_identical_across_probe_backends(impl):
    """End-to-end: a store on a device probe answers exactly like numpy."""
    rng = np.random.default_rng(7)
    sigs = rng.integers(0, 1 << 16, (120, 64), dtype=np.int32)
    sigs[100] = sigs[3]
    cfg = StoreConfig(k=64, n_bands=16, rows_per_band=4)
    a = SketchStore(cfg)
    b = SketchStore(cfg, probe_impl=impl)
    a.add(sigs)
    b.add(sigs)
    q = np.concatenate([sigs[:8],
                        rng.integers(1 << 20, 1 << 24, (2, 64),
                                     dtype=np.int32)])
    ia, sa = a.query(q, top_k=5)
    ib, sb = b.query(q, top_k=5)
    assert np.array_equal(ia, ib)
    assert np.array_equal(sa, sb)
