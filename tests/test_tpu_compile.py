"""Compile the served path's device legs for a described TPU v5e.

Each test asks ``kernels.dispatch`` which impl a served leg resolves to under
``backend="tpu"`` and compiles exactly that at real widths (D = 2^16,
K = 256, 32 bands x 8 rows, a 256-query batch, 2^20 slots per band) for one
chip of a v5e topology that is described, not attached.  What the TPU
compiler refuses here — unaligned blocks, value-level dynamic slices, VMEM
overruns — would otherwise first show up as a failed chip run.  Nothing
runs, so nothing here says anything about results or speed.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, dispatch, lsh_probe, query_fused
from repro.kernels.cminhash_sparse import cminhash_sparse_pallas
from repro.kernels.collision_kernel import collision_count_pallas

D, K, NB, R, Q, NNZ = 1 << 16, 256, 32, 8, 256, 256
N_SLOTS, WIDTH = 1 << 20, 8


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip can be written to it but not
    read back here."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, compiled.as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("b,k", [
    (8192, 128),    # dedup-k128 bulk ingest batches
    (Q, K),         # search-k256 stream batches
    (16, 128),      # dedup-k128 lookup batches
])
def test_sparse_sign_compiles(one_chip, b, k):
    assert dispatch.select_sparse_impl(backend="tpu") == "pallas"
    blocks = autotune.recommend("sparse_pallas", b, D, k, backend="tpu",
                                nnz=NNZ)
    _, hlo = _compile(
        partial(cminhash_sparse_pallas, k=k, pack_b=32, interpret=False,
                **blocks),
        _spec(one_chip, (b, NNZ), jnp.int32), _spec(one_chip, (D,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_fold_compiles(one_chip):
    assert dispatch.select_query_impl(backend="tpu") == "pallas"
    plane = _spec(one_chip, (Q, NB, R), jnp.uint32)
    _, hlo = _compile(partial(query_fused.fold_planes_pallas,
                              interpret=False), plane, plane)
    assert "tpu_custom_call" in hlo


def test_probe_compiles_at_served_slots(one_chip):
    # the Pallas probe's VMEM-resident records cannot fit at this size, so
    # TPU dispatch resolves the probe leg to the compiled-jnp twin
    assert dispatch.select_probe_impl(backend="tpu") == "jnp"
    compiled, _ = _compile(
        partial(lsh_probe.lsh_probe_jnp, n_slots=N_SLOTS, max_probes=16),
        _spec(one_chip, (NB * N_SLOTS, 2 + WIDTH), jnp.int32),
        _spec(one_chip, (Q * NB, lsh_probe.META_COLS), jnp.int32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= NB * N_SLOTS * (2 + WIDTH) * 4


def test_score_topk_compiles(one_chip):
    compiled, _ = _compile(
        partial(query_fused.score_topk, k=K, b=32, top_k=10),
        _spec(one_chip, (Q, NB * WIDTH), jnp.int32),
        _spec(one_chip, (1 << 20, K), jnp.uint32),
        _spec(one_chip, (Q, K), jnp.uint32))
    # a (Q, C, W) candidate-row gather and its unpack are the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_brute_collision_compiles(one_chip):
    _, hlo = _compile(partial(collision_count_pallas, interpret=False),
                      _spec(one_chip, (Q, K), jnp.int32),
                      _spec(one_chip, (16384, K), jnp.int32))
    assert "tpu_custom_call" in hlo
