"""The scan-aware HLO cost analyzer — pinned against XLA's own cost_analysis
on scan-free modules and against analytic counts with scans + collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo


def test_matches_xla_on_scan_free_module():
    def f(x, w):
        return jnp.tanh(x @ w)

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    got = hlo.analyze(c.as_text())
    ref = c.cost_analysis()
    assert got.flops == pytest.approx(ref["flops"], rel=0.02)
    # the naive model reproduces XLA's every-op accounting
    assert got.bytes_naive == pytest.approx(ref["bytes accessed"], rel=0.1)
    assert got.collective_bytes == 0


def test_fused_bytes_ignore_elementwise_chains():
    """Elementwise work inside a scan body is free under the TPU-fusion proxy
    but piles up per trip under naive accounting. (A straight-line chain gets
    fused by XLA:CPU itself, so the scan keeps the ops distinct.)

    The premise — "naive accounting sees the body's work once per trip" —
    depends on how this XLA version lays the body out (direct ops, per-op
    kLoop fusions, or one fused call), so it is gated on *observed* HLO
    behavior, not a version check: if doubling the trip count does not grow
    naive bytes, this XLA emits the body in a form the naive model cannot
    see per-trip work in, and the naive-vs-fused contrast is untestable.
    """
    def body(y, _):
        y = jnp.tanh(y) * 1.01 + 0.1
        y = jnp.exp(y * 0.1) - 1.0
        return y, None

    def compiled(length):
        def f(x):
            y, _ = jax.lax.scan(body, x, None, length=length)
            return y
        x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        return jax.jit(f).lower(x).compile()

    got = hlo.analyze(compiled(30).as_text())
    doubled = hlo.analyze(compiled(60).as_text())
    if doubled.bytes_naive < 1.5 * got.bytes_naive:
        pytest.skip("this XLA emits the scan body in a form whose per-trip "
                    "buffers are invisible to naive accounting")
    assert got.bytes < got.bytes_naive / 3, (got.bytes, got.bytes_naive)


def test_scan_trip_count_multiplies():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    for L in (4, 16):
        ws = jax.ShapeDtypeStruct((L, 256, 256), jnp.float32)
        c = jax.jit(f).lower(x, ws).compile()
        got = hlo.analyze(c.as_text())
        ref = c.cost_analysis()
        assert got.flops == pytest.approx(L * ref["flops"], rel=0.05), L


def test_nested_scans_multiply():
    def inner_body(c, _):
        return jnp.tanh(c @ c), None

    def outer_body(x, _):
        y, _ = jax.lax.scan(inner_body, x, None, length=3)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer_body, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    got = hlo.analyze(c.as_text())
    dot_flops = 2 * 64 * 64 * 64
    assert got.flops == pytest.approx(15 * dot_flops, rel=0.05)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >1 device")
def test_collectives_exact():  # exercised in the subprocess sharding test
    pass


def test_collective_formula_in_sharded_scan(tmp_path):
    """Subprocess with 8 CPU devices: all-reduce wire bytes inside a scan must
    match the analytic ring formula exactly."""
    import subprocess
    import sys
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis import hlo

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def layer(x, w):
    w1, w2 = w
    return jnp.tanh(x @ w1) @ w2, None

def f(x, ws):
    y, _ = jax.lax.scan(layer, x, ws)
    return y

L, B, D, F = 6, 64, 128, 512
x = jax.ShapeDtypeStruct((B, D), jnp.float32)
ws = (jax.ShapeDtypeStruct((L, D, F), jnp.float32),
      jax.ShapeDtypeStruct((L, F, D), jnp.float32))
with mesh:
    c = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P("data", None)),
        (NamedSharding(mesh, P(None, None, "model")),
         NamedSharding(mesh, P(None, "model", None))),
    )).lower(x, ws).compile()
got = hlo.analyze(c.as_text())
expected = L * 2 * (4 - 1) / 4 * (B // 2) * D * 4   # ring all-reduce / layer
assert abs(got.collective_bytes - expected) / expected < 1e-6, \
    (got.collective_bytes, expected)
exp_flops = L * 2 * (2 * (B // 2) * D * (F // 4))
assert abs(got.flops - exp_flops) / exp_flops < 0.05, (got.flops, exp_flops)
print("OK")
"""
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=".")
    assert "OK" in p.stdout, p.stdout + p.stderr
