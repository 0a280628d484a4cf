"""Autotuner: cache semantics (recommend never measures; measure caches the
winner; JSON persistence via $REPRO_AUTOTUNE_CACHE) and engine integration."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import SketchConfig, SketchEngine
from repro.kernels import autotune


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def test_recommend_heuristic_on_miss():
    blocks = autotune.recommend("dense_int8", 8, 4096, 256, backend="cpu")
    assert set(blocks) == {"block_b", "block_d"}
    assert blocks["block_d"] % 32 == 0
    # clamped to the shape: tiny batch cannot get a giant batch tile
    small = autotune.recommend("dense_int8", 1, 64, 16, backend="cpu")
    assert small["block_b"] == 1
    with pytest.raises(ValueError):
        autotune.recommend("nope", 1, 1, 1, backend="cpu")


def test_measure_caches_winner():
    cands = ({"block_j": 4}, {"block_j": 8})
    best = autotune.measure("sparse_windows", 2, 256, 32, candidates=cands,
                            warmup=1, iters=1)
    assert best in [dict(c) for c in cands]
    assert autotune.cached("sparse_windows", 2, 256, 32) == best
    # recommend now returns the measured winner, not the heuristic
    assert autotune.recommend("sparse_windows", 2, 256, 32) == best
    # bucketing: a same-pow2-class shape hits the same entry
    assert autotune.cached("sparse_windows", 2, 200, 30) == best
    assert autotune.cached("sparse_windows", 2, 1024, 32) is None
    # nnz is part of the sparse key: a different density re-tunes
    assert autotune.cached("sparse_windows", 2, 256, 32, nnz=512) is None
    # measure() is sweep-on-MISS: a cached shape class returns immediately
    # (different candidate list would win if it re-swept)
    again = autotune.measure("sparse_windows", 2, 256, 32,
                             candidates=({"block_j": 2},), warmup=0, iters=1)
    assert again == best
    forced = autotune.measure("sparse_windows", 2, 256, 32, force=True,
                              candidates=({"block_j": 2},), warmup=0, iters=1)
    assert forced == {"block_j": 2}


def test_measure_guard_rejects_slow_winner(monkeypatch):
    """A default-sweep winner that cannot beat the heuristic default in the
    confirmation duel must NOT be cached — the default is, and the rejection
    is counted (regression: a cached noise artifact made every later
    recommend() slower than not tuning at all)."""
    from repro.obs import metrics as obs_metrics

    default = {"block_j": 64}
    sweeps = []

    def fake_sweep(runner, cands, warmup, iters):
        sweeps.append([dict(c) for c in cands])
        if len(sweeps) == 1:       # full sweep: a non-default "winner"
            return (1e-9, next(c for c in cands if c != default))
        return (1e-9, default)     # duel: the default is actually faster

    monkeypatch.setattr(autotune, "_sweep", fake_sweep)
    reg = obs_metrics.default()
    before = reg.counter("autotune.guard_rejects").value
    best = autotune.measure("sparse_windows", 64, 256, 32,
                            warmup=0, iters=1)
    assert best == default
    assert autotune.cached("sparse_windows", 64, 256, 32) == default
    assert reg.counter("autotune.guard_rejects").value == before + 1
    assert len(sweeps) == 2 and sorted(
        map(str, sweeps[1])) == sorted(map(str, [sweeps[0][0], default]))
    # the default rides in the sweep field even though _CANDIDATES lacks it
    assert default in sweeps[0]


def test_measure_guard_confirms_fast_winner(monkeypatch):
    """A winner that survives the duel is cached as-is, no rejection."""
    from repro.obs import metrics as obs_metrics

    winner = {"block_j": 16}

    def fake_sweep(runner, cands, warmup, iters):
        return (1e-9, winner)

    monkeypatch.setattr(autotune, "_sweep", fake_sweep)
    reg = obs_metrics.default()
    before = reg.counter("autotune.guard_rejects").value
    assert autotune.measure("sparse_windows", 64, 512, 32,
                            warmup=0, iters=1) == winner
    assert autotune.cached("sparse_windows", 64, 512, 32) == winner
    assert reg.counter("autotune.guard_rejects").value == before


def test_measure_explicit_candidates_bypass_guard(monkeypatch):
    """Explicit candidates= pins the field: no default injection, no duel —
    the caller's winner is trusted verbatim even if slower than default."""
    def boom(*a, **k):
        raise AssertionError("guard duel must not run for explicit sweeps")

    monkeypatch.setattr(autotune, "_duel", boom)
    best = autotune.measure("sparse_windows", 64, 1024, 32,
                            candidates=({"block_j": 2},), warmup=0, iters=1)
    assert best == {"block_j": 2}
    assert autotune.cached("sparse_windows", 64, 1024, 32) == {"block_j": 2}


def test_cache_persists_to_json(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    best = autotune.measure("sparse_windows", 2, 128, 16,
                            candidates=({"block_j": 4},), warmup=0, iters=1)
    assert best == {"block_j": 4}
    data = json.loads(path.read_text())
    assert any(k.startswith("sparse_windows:") for k in data)
    # a fresh process (cleared in-memory cache) reloads the file
    autotune.clear_cache()
    assert autotune.cached("sparse_windows", 2, 128, 16) == best


def test_measure_dense_kinds_tiny():
    cands = ({"block_b": 2, "block_d": 32},)
    for kind in ("dense_int8", "dense_packed"):
        best = autotune.measure(kind, 2, 64, 16, candidates=cands,
                                warmup=0, iters=1)
        assert best == {"block_b": 2, "block_d": 32}, kind


def test_engine_autotune_measure_populates_cache():
    cfg = SketchConfig(d=256, k=32, autotune_measure=True, use_kernel=True,
                       seed=0)
    eng = SketchEngine(cfg)
    idx = jnp.asarray(np.array([[3, 17, 200, -1]], np.int32))
    sig = eng.signatures_sparse(idx)
    kind = ("sparse_pallas" if jax.default_backend() == "tpu"
            else "sparse_windows")
    assert autotune.cached(kind, 1, 256, 32, nnz=idx.shape[1]) is not None
    # values unchanged vs the untuned engine
    eng2 = SketchEngine(SketchConfig(d=256, k=32, seed=0))
    assert np.array_equal(np.asarray(sig), np.asarray(
        eng2.signatures_sparse(idx)))


def test_sweep_raises_when_no_candidate_compiles(monkeypatch):
    """A kernel that compiles at no block size must fail loudly — dropping
    every candidate and caching nothing would let a broken kernel pass."""
    def broken_runner(kind, b, d, k, nnz, seed):
        def thunk_for(blocks):
            def fn():
                raise ValueError(f"refused block {blocks}")
            return fn
        return thunk_for

    monkeypatch.setattr(autotune, "_make_runner", broken_runner)
    with pytest.raises(RuntimeError, match="no block candidate compiled"):
        autotune.measure("sparse_pallas", 64, 1024, 32, warmup=0, iters=1)
    assert autotune.cached("sparse_pallas", 64, 1024, 32) is None


@pytest.mark.parametrize("kind", autotune.KINDS)
def test_tpu_candidates_tile_the_lanes(kind):
    """Every TPU sweep candidate and default is (8, 128)-aligned where the
    block is a sublane/lane dim of a Pallas block."""
    pool = autotune._candidates_for(kind, "tpu")
    if kind in ("dense_int8", "sparse_pallas"):
        pool = pool + (autotune._DEFAULTS[kind],)
    for blocks in pool:
        assert blocks.get("block_b", 8) % 8 == 0, (kind, blocks)
        assert blocks.get("block_d", 128) % 128 == 0, (kind, blocks)
        if kind == "sparse_pallas":
            assert blocks["block_j"] % 128 == 0, blocks
    clamped = autotune.recommend("dense_int8", 64, 100, 16, backend="tpu")
    assert clamped["block_d"] == 128
