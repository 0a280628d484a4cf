"""Block-size autotuner for the signing kernels.

Keys: ``(kind, backend, pow2-bucketed B/D/K)`` — shapes are bucketed to the
next power of two so one measurement serves a whole shape class.  Kinds:

* ``dense_int8``   -> {block_b, block_d}   (kernels.cminhash_kernel)
* ``dense_packed`` -> {block_b, block_d}   (kernels.cminhash_packed)
* ``sparse_pallas``-> {block_b, block_j}   (kernels.cminhash_sparse, Pallas)
* ``sparse_windows``-> {block_j}           (kernels.cminhash_sparse, jnp)
* ``query_fold``   -> {block_q}            (kernels.query_fused, Pallas fold;
                                            keyed B=queries, D=n_bands,
                                            K=rows_per_band)
* ``probe_pallas`` -> {block_e}            (kernels.lsh_probe, Pallas probe;
                                            keyed B=meta entries, D=n_slots,
                                            K=record width)

Cache semantics (documented contract, see kernels/README.md):

* ``recommend()`` never measures.  It returns the cached winner when one
  exists, else a shape-clamped heuristic default.  This is what the engine
  and dispatch layer call on every signing request — cheap and deterministic.
* ``measure()`` times every valid candidate on synthetic data of the request
  shape (interleaved min-of-``iters`` rounds — shared-box noise hits all
  candidates equally, see kernels/dispatch.py), stores the winner in the
  in-process cache, and appends it to the JSON file at
  ``$REPRO_AUTOTUNE_CACHE`` (if set) so later processes start warm.
* Default sweeps (``candidates=None``) always include the clamped heuristic
  default and re-duel the would-be winner against it head-to-head before
  caching: a winner that cannot beat the default in the duel is REJECTED
  (``autotune.guard_rejects`` counter) and the default is cached instead.
  This guards against caching a noise artifact that would then make every
  later ``recommend()`` slower than not tuning at all (seen in practice:
  a cached ``block_j=128`` 1.6x slower than the un-tuned default).
  Explicit ``candidates=`` sweeps are trusted verbatim — no default
  injection, no guard — so callers can force a specific winner.
* The JSON file is loaded lazily once per path and merged under the
  in-process entries; ``clear_cache()`` forgets both (the file is untouched).

Benchmarks (and ``SketchConfig(autotune_measure=True)``) run ``measure``;
everything else rides the cache.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.obs import metrics as obs_metrics

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

KINDS = ("dense_int8", "dense_packed", "sparse_pallas", "sparse_windows",
         "query_fold", "probe_pallas")

# defaults tile the TPU's (8, 128) layout wherever the block is a sublane or
# lane dim of a Pallas block (block_b, block_d, block_j); block_q is rounded
# up to whole (8, 128) tiles inside the fold kernel
_DEFAULTS: dict[str, dict[str, int]] = {
    "dense_int8": {"block_b": 8, "block_d": 256},
    "dense_packed": {"block_b": 8, "block_d": 256},
    "sparse_pallas": {"block_b": 8, "block_j": 128},
    "sparse_windows": {"block_j": 64},
    "query_fold": {"block_q": 128},
    "probe_pallas": {"block_e": 128},
}

_CANDIDATES: dict[str, tuple[dict[str, int], ...]] = {
    "dense_int8": tuple({"block_b": bb, "block_d": bd}
                        for bb in (4, 8, 16) for bd in (128, 256, 512)),
    "dense_packed": tuple({"block_b": bb, "block_d": bd}
                          for bb in (4, 8, 16) for bd in (128, 256, 512)),
    "sparse_pallas": tuple({"block_b": bb, "block_j": bj}
                           for bb in (4, 8, 16) for bj in (16, 32, 64)),
    "sparse_windows": tuple({"block_j": bj}
                            for bj in (16, 32, 64, 128, 256)),
    "query_fold": tuple({"block_q": bq} for bq in (32, 64, 128, 256, 512)),
    "probe_pallas": tuple({"block_e": be} for be in (32, 64, 128, 256, 512)),
}

# On TPU only blocks that tile (8, 128) compile.  The packed dense kernel
# and the Pallas probe are never dispatched there (kernels/dispatch.py), so
# they have nothing to sweep.
_TPU_CANDIDATES: dict[str, tuple[dict[str, int], ...]] = {
    "dense_int8": tuple({"block_b": bb, "block_d": bd}
                        for bb in (8, 16, 32) for bd in (128, 256, 512)),
    "dense_packed": (),
    "sparse_pallas": tuple({"block_b": bb, "block_j": bj}
                           for bb in (8, 16) for bj in (128, 256)),
    "sparse_windows": _CANDIDATES["sparse_windows"],
    "query_fold": tuple({"block_q": bq} for bq in (32, 64, 128, 256, 512)),
    "probe_pallas": (),
}


def _candidates_for(kind: str, backend: str) -> tuple[dict[str, int], ...]:
    """The default sweep field for ``kind`` on ``backend``."""
    return (_TPU_CANDIDATES if backend == "tpu" else _CANDIDATES)[kind]

_cache: dict[str, dict[str, int]] = {}
_loaded_paths: set[str] = set()


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def cache_key(kind: str, b: int, d: int, k: int, backend: str,
              nnz: int = 0) -> str:
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r} (want one of {KINDS})")
    key = f"{kind}:{backend}:B{_pow2(b)}:D{_pow2(d)}:K{_pow2(k)}"
    if kind.startswith("sparse"):
        # nnz is the dimension block_j tiles — a winner at one density is
        # not a winner at another, so it belongs in the key
        key += f":N{_pow2(max(nnz, 1))}"
    return key


def _cache_path() -> str | None:
    return os.environ.get(CACHE_ENV) or None


def _load_file(path: str) -> None:
    if path in _loaded_paths:
        return
    _loaded_paths.add(path)
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return
    for key, blocks in data.items():
        _cache.setdefault(key, {str(n): int(v) for n, v in blocks.items()})


def _save_file(path: str) -> None:
    try:
        existing: dict[str, Any] = {}
        if os.path.exists(path):
            with open(path) as f:
                existing = json.load(f)
        existing.update(_cache)
        with open(path, "w") as f:
            json.dump(existing, f, indent=1, sort_keys=True)
    except (OSError, ValueError):
        pass                        # cache persistence is best-effort


def clear_cache() -> None:
    """Forget in-process entries and loaded-file markers (file untouched)."""
    _cache.clear()
    _loaded_paths.clear()


def cached(kind: str, b: int, d: int, k: int, backend: str | None = None,
           nnz: int = 0) -> dict[str, int] | None:
    backend = backend or jax.default_backend()
    path = _cache_path()
    if path:
        _load_file(path)
    hit = _cache.get(cache_key(kind, b, d, k, backend, nnz))
    return dict(hit) if hit else None


def _clamp(kind: str, blocks: dict[str, int], b: int, d: int,
           k: int, backend: str = "cpu") -> dict[str, int]:
    out = dict(blocks)
    if "block_b" in out:
        # a batch tile of pow2(b) >= b rows is the whole padded batch, which
        # TPU accepts at any size; larger batches keep the aligned tile
        out["block_b"] = max(1, min(out["block_b"], _pow2(b)))
    if "block_d" in out:
        # dense kernels want block_d % 32 == 0 (bit-packed words), and a
        # whole 128-lane tile on TPU; never clamp below that
        lane = 128 if backend == "tpu" else 32
        out["block_d"] = max(lane, min(out["block_d"], _pow2(max(d, lane))))
    if "block_j" in out:
        out["block_j"] = max(1, out["block_j"])
    if "block_q" in out:
        # fold tiles the query batch (keyed as B)
        out["block_q"] = max(1, min(out["block_q"], _pow2(b)))
    if "block_e" in out:
        # probe tiles the flat (Q * n_bands) meta entries (keyed as B)
        out["block_e"] = max(1, min(out["block_e"], _pow2(b)))
    return out


def recommend(kind: str, b: int, d: int, k: int,
              backend: str | None = None, nnz: int = 0) -> dict[str, int]:
    """Cached winner if one exists, else a shape-clamped heuristic. Never
    measures."""
    backend = backend or jax.default_backend()
    hit = cached(kind, b, d, k, backend, nnz)
    if hit is not None:
        obs_metrics.default().counter("autotune.hit").inc()
        return _clamp(kind, hit, b, d, k, backend)
    obs_metrics.default().counter("autotune.heuristic").inc()
    return _clamp(kind, _DEFAULTS[kind], b, d, k, backend)


def _make_runner(kind: str, b: int, d: int, k: int, nnz: int,
                 seed: int) -> Callable[[dict[str, int]], Any]:
    """Build synthetic inputs once; return blocks -> timed thunk."""
    import jax.numpy as jnp

    from ..core.permutations import make_two_permutations
    from . import dispatch

    rng = np.random.default_rng(seed)
    interpret = jax.default_backend() != "tpu"

    if kind == "query_fold":
        # b=queries, d=n_bands, k=rows_per_band (uint32 words per band)
        from . import query_fused
        lo = jnp.asarray(rng.integers(0, 2**32, (b, d, max(k, 1)),
                                      dtype=np.uint32))
        hi = jnp.zeros_like(lo)
        return lambda blocks: (lambda: query_fused.fold_planes_pallas(
            hi, lo, interpret=interpret, **blocks))
    if kind == "probe_pallas":
        # b=meta entries, d=n_slots, k=record width W
        from . import lsh_probe
        n_slots = max(1, d)
        records = jnp.full((n_slots, 2 + max(k, 1)), -1, jnp.int32)
        hashes = rng.integers(0, 2**63, (max(b, 1), 1), dtype=np.uint64)
        meta = jnp.asarray(lsh_probe.probe_operands(hashes, n_slots))
        return lambda blocks: (lambda: lsh_probe.lsh_probe_pallas(
            records, meta, n_slots=n_slots, max_probes=8,
            interpret=interpret, **blocks))

    _, pi = make_two_permutations(jax.random.PRNGKey(seed), d)
    impl = {"dense_int8": "int8", "dense_packed": "packed",
            "sparse_pallas": "pallas", "sparse_windows": "windows"}[kind]

    if kind.startswith("dense"):
        dens = (nnz / d) if nnz else 0.05
        v = jnp.asarray((rng.random((b, d)) < dens).astype(np.int8))
        return lambda blocks: (lambda: dispatch.signatures_dense(
            v, pi, k, impl=impl, **blocks))
    nnz = max(1, nnz or int(0.05 * d))
    idx = jnp.asarray(np.sort(
        rng.integers(0, d, (b, nnz)).astype(np.int32), axis=1))
    return lambda blocks: (lambda: dispatch.signatures_sparse(
        idx, pi, k, impl=impl, **blocks))


def _valid(kind: str, blocks: dict[str, int], b: int, d: int, k: int) -> bool:
    return not ("block_d" in blocks and blocks["block_d"] % 32)


def _sweep(runner: Callable[[dict[str, int]], Any],
           cands: list[dict[str, int]], warmup: int,
           iters: int) -> tuple[float, dict[str, int]] | None:
    """Time candidates INTERLEAVED (round-robin min-of-``iters``): on a
    shared box, drift and noise bursts then hit every candidate equally
    instead of penalizing whichever ran during the burst — the same
    convention bench_sign.py uses (see kernels/dispatch.py).  A candidate
    that raises during warmup is dropped (invalid on this backend); one that
    raises mid-round keeps its best earlier time.  Returns the fastest
    ``(seconds, blocks)`` or None when nothing ran.

    Raises when no candidate compiles at all: a kernel that lowers at no
    block size is broken on this backend, not a tuning result."""
    import math

    live: list[tuple[dict[str, int], Any, list[float]]] = []
    errors: list[str] = []
    for blocks in cands:
        fn = runner(blocks)
        try:
            for _ in range(max(warmup, 1)):
                jax.block_until_ready(fn())
        except Exception as e:             # candidate invalid on this backend
            errors.append(f"{blocks}: {type(e).__name__}: {e}"[:300])
            continue
        live.append((blocks, fn, [math.inf]))
    if not live:
        raise RuntimeError(
            f"no block candidate compiled on {jax.default_backend()} "
            f"({len(cands)} tried): " + "; ".join(errors[:3]))
    for _ in range(max(iters, 1)):
        for blocks, fn, t in live:
            try:
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                t[0] = min(t[0], time.perf_counter() - t0)
            except Exception:
                pass
    live = [(blocks, fn, t) for blocks, fn, t in live if t[0] < math.inf]
    if not live:
        return None
    blocks, _, t = min(live, key=lambda e: e[2][0])
    return (t[0], blocks)


def _duel(runner: Callable[[dict[str, int]], Any],
          winner: dict[str, int], default: dict[str, int], warmup: int,
          iters: int) -> bool:
    """Head-to-head re-measurement of the sweep winner against the heuristic
    default.  True iff the winner is strictly faster — i.e. the sweep result
    survives confirmation and deserves the cache slot."""
    best = _sweep(runner, [winner, default], warmup, iters)
    return best is not None and best[1] == winner


def measure(kind: str, b: int, d: int, k: int, *, backend: str | None = None,
            nnz: int = 0, warmup: int = 1, iters: int = 3,
            candidates: tuple[dict[str, int], ...] | None = None,
            seed: int = 0, force: bool = False) -> dict[str, int]:
    """Sweep-and-cache on miss: time every valid candidate at this shape and
    cache the winner — but return a cached winner immediately when one exists
    (``force=True`` re-sweeps), so engines with ``autotune_measure`` pay for
    the sweep once per shape class, not once per batch.

    Default sweeps (``candidates=None``) include the clamped heuristic
    default in the field and re-duel the winner against it before caching;
    a winner that loses the duel is rejected (``autotune.guard_rejects``)
    and the default is cached instead — a cached "winner" must never make
    ``recommend()`` slower than not tuning at all.  Explicit ``candidates=``
    bypass both the injection and the guard (the caller pins the field).

    ``nnz`` sizes the synthetic sparse inputs (and enters the sparse cache
    key); 0 means a 5% density default."""
    backend = backend or jax.default_backend()
    if not force:
        hit = cached(kind, b, d, k, backend, nnz)
        if hit is not None:
            obs_metrics.default().counter("autotune.hit").inc()
            return hit
    obs_metrics.default().counter("autotune.sweeps").inc()
    sweep_t0 = time.perf_counter()
    runner = _make_runner(kind, b, d, k, nnz, seed)
    guard = candidates is None
    default = _clamp(kind, _DEFAULTS[kind], b, d, k, backend)
    field: list[dict[str, int]] = []
    seen: set[tuple] = set()     # clamping can collapse candidates; time once
    pool = _candidates_for(kind, backend) + (default,) if guard \
        else candidates
    for cand in pool:
        blocks = _clamp(kind, cand, b, d, k, backend)
        key = tuple(sorted(blocks.items()))
        if key in seen or not _valid(kind, blocks, b, d, k):
            continue
        seen.add(key)
        field.append(blocks)
    best = _sweep(runner, field, warmup, iters)
    if best is not None:
        blocks = best[1]
        if guard and blocks != default and not _duel(
                runner, blocks, default, warmup, max(iters, 3)):
            obs_metrics.default().counter("autotune.guard_rejects").inc()
            blocks = default
        best = (best[0], blocks)
    obs_metrics.default().histogram("autotune.sweep").observe(
        time.perf_counter() - sweep_t0)
    if best is None:
        return recommend(kind, b, d, k, backend, nnz)
    _cache[cache_key(kind, b, d, k, backend, nnz)] = dict(best[1])
    path = _cache_path()
    if path:
        _save_file(path)
    return dict(best[1])
