"""SketchEngine — mesh-sharded batched C-MinHash signature computation.

The production entry point for the data pipeline: holds the paper's two
permutations and routes every batch — dense or sparse — through the kernel
dispatch layer (``kernels.dispatch``: shape/backend implementation selection
plus autotuned block sizes), sharded over the ``data`` mesh axis with
pi/sigma replicated — they are the whole point: two vectors, trivially
replicable even at D = 2^30.  On a mesh the signing call runs under
``jax.shard_map``: each device signs its own batch shard with the kernel on
local shapes, because a Pallas (Mosaic) call is opaque to XLA's SPMD
partitioner and cannot be split across devices by it.

``sign_packed`` is the fused ingest path: signatures leave the kernel already
truncated to b bits and packed into uint32 words (``SketchStore.add_packed``
consumes them), so the (B, K) int32 form never reaches the host.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import dispatch
from ..obs import metrics as obs_metrics
from .permutations import make_two_permutations

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SketchConfig:
    d: int                      # universe size (shingle space)
    k: int = 1024               # signature length
    use_sigma: bool = True      # C-MinHash-(sigma,pi) vs -(0,pi)
    use_kernel: bool = True     # kernel dispatch vs jnp reference paths
    block_b: int | None = None  # None -> autotune cache / heuristic
    block_d: int | None = None  # (dense kernels)
    block_j: int | None = None  # (sparse kernels: nnz tile)
    autotune_measure: bool = False  # sweep-and-cache blocks on cache miss
    seed: int = 0


class SketchEngine:
    """Batched signer. ``mesh=None`` -> single device; else batch shards over 'data'
    (and 'pod' when present) with pi/sigma replicated."""

    def __init__(self, cfg: SketchConfig, mesh: jax.sharding.Mesh | None = None):
        self.cfg = cfg
        self.mesh = mesh
        key = jax.random.PRNGKey(cfg.seed)
        sigma, pi = make_two_permutations(key, cfg.d)
        self.pi = pi
        self.sigma = sigma if cfg.use_sigma else None

        if mesh is not None:
            batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            self._batch_spec = P(batch_axes)
            self._data_sharding = NamedSharding(mesh, self._batch_spec)
            self._rep_sharding = NamedSharding(mesh, P())
            self.pi = jax.device_put(self.pi, self._rep_sharding)
            if self.sigma is not None:
                self.sigma = jax.device_put(self.sigma, self._rep_sharding)
        else:
            self._data_sharding = None
        self._mesh_fns: dict = {}       # (layout, pack_b) -> jit(shard_map)
        # sign-call counters (dispatch counts per resolved kernel impl;
        # these count what the engine was ASKED, rows included, so
        # rows/impl ratios read straight off one snapshot)
        reg = obs_metrics.default()
        self._c_dense = reg.counter("engine.sign.dense")
        self._c_sparse = reg.counter("engine.sign.sparse")
        self._c_rows = reg.counter("engine.sign.rows")

    def signatures_dense(self, v: Array, *, pack_b: int | None = None) -> Array:
        """(B, D) binary -> (B, K) int32 signatures ((B, W) uint32 packed
        words when ``pack_b`` is set — the fused sign->pack kernel path)."""
        self._c_dense.inc()
        self._c_rows.inc(v.shape[0])
        return self._signed("dense", v, pack_b)

    def signatures_sparse(self, idx: Array, *,
                          pack_b: int | None = None) -> Array:
        """(B, NNZ) padded index lists -> (B, K) int32 signatures ((B, W)
        uint32 packed words when ``pack_b`` is set)."""
        self._c_sparse.inc()
        self._c_rows.inc(idx.shape[0])
        return self._signed("sparse", idx, pack_b)

    def _sign_local(self, layout: str, x: Array, pi: Array,
                    sigma: Array | None, pack_b: int | None) -> Array:
        """One device's signing call through the dispatch front door."""
        cfg = self.cfg
        if layout == "dense":
            return dispatch.signatures_dense(
                x, pi, cfg.k, sigma, use_kernel=cfg.use_kernel,
                pack_b=pack_b, block_b=cfg.block_b, block_d=cfg.block_d,
                autotune_measure=cfg.autotune_measure)
        return dispatch.signatures_sparse(
            x, pi, cfg.k, sigma, use_kernel=cfg.use_kernel, pack_b=pack_b,
            block_b=cfg.block_b, block_j=cfg.block_j,
            autotune_measure=cfg.autotune_measure)

    def _signed(self, layout: str, x: Array, pack_b: int | None) -> Array:
        if self.mesh is None:
            return self._sign_local(layout, x, self.pi, self.sigma, pack_b)
        perms = (self.pi,) if self.sigma is None else (self.pi, self.sigma)
        fn = self._mesh_fns.get((layout, pack_b))
        if fn is None:
            def body(x, pi, *sigma):
                return self._sign_local(layout, x, pi,
                                        sigma[0] if sigma else None, pack_b)
            fn = jax.jit(jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(self._batch_spec,) + (P(),) * len(perms),
                out_specs=self._batch_spec, check_vma=False))
            self._mesh_fns[(layout, pack_b)] = fn
        return fn(jax.device_put(x, self._data_sharding), *perms)

    def sign_packed(self, data: Array, b: int, *,
                    layout: str = "dense") -> Array:
        """Fused sign->pack ingest: data -> (B, ceil(K/(32/b))) uint32 words.

        Bit-identical to ``pack_codes(signatures_*(data), b)``; every impl
        packs inside its own jit — no (B, K) int32 on the host.
        Feed the result to ``SketchStore.add_packed``.
        """
        if layout == "dense":
            return self.signatures_dense(data, pack_b=b)
        if layout == "sparse":
            return self.signatures_sparse(data, pack_b=b)
        raise ValueError(f"unknown layout {layout!r}")

    def sign(self, data: Array, *, layout: str = "sparse",
             pack_b: int | None = None) -> Array:
        """One signing front door: layout x (packed | raw) in one call.

        Returns a **device array without syncing** — JAX dispatch is
        asynchronous on every backend, so the computation runs in the
        background until someone materializes the result
        (``np.asarray``/``block_until_ready``).  That gap is what
        ``serve.search.IngestPipeline`` overlaps: batch N+1's signing
        executes while batch N's host-side scatter is still running.  Keep
        batch shapes uniform — each distinct shape compiles its own
        executable.
        """
        if pack_b is not None:
            return self.sign_packed(data, pack_b, layout=layout)
        if layout == "dense":
            return self.signatures_dense(data)
        if layout == "sparse":
            return self.signatures_sparse(data)
        raise ValueError(f"unknown layout {layout!r}")

    @functools.cached_property
    def parameter_bytes(self) -> int:
        """Memory for the hashing parameters — the paper's headline win."""
        n = 2 if self.sigma is not None else 1
        return n * self.cfg.d * 4

    @staticmethod
    def classical_parameter_bytes(d: int, k: int) -> int:
        """What Algorithm 1 would need instead."""
        return k * d * 4
