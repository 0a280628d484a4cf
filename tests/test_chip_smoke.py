"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusal to
report anything from a machine without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_chip_smoke_phases_tiny(monkeypatch, capsys):
    """The whole one-chip flow through main(), with the platform check
    steered to this backend and the corpus cut to a few hundred docs."""
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM",
                        jax.default_backend())
    monkeypatch.setattr(chip_smoke, "setup_compile_cache", lambda: "off")
    reports = []
    real = chip_smoke.run_one_chip

    def tiny(meter, **_):
        r = real(meter, n_docs=768, batch=256, n_queries=128,
                                    n_oracle=32, n_stream=24,
                                    rate_qps=2000.0, seed=3)
        reports.append(r)
        return r

    monkeypatch.setattr(chip_smoke, "run_one_chip", tiny)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": jax.default_backend(),
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    (r,) = reports
    assert r["query"]["top1_self_hit"] == 1.0
    assert r["oracle"]["identical"] is True
    assert r["stream"]["rejected"] == 0 and r["stream"]["wrong"] == 0
    assert r["resident_bytes"]["words"] == 768 * 256 * 4
    assert any(n.startswith("kernel.sparse.") for n in r["impls"])
    for name in ("kernels", "corpus", "ingest", "upload", "query",
                 "oracle", "stream"):
        assert r[name]["wall_s"] >= r[name]["compile_s"] >= 0.0, name


def test_chip_smoke_failed_phase_exits_nonzero(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM",
                        jax.default_backend())
    monkeypatch.setattr(chip_smoke, "setup_compile_cache", lambda: "off")

    def broken(meter, **_):
        chip_smoke.check(False, "planted failure")

    monkeypatch.setattr(chip_smoke, "run_one_chip", broken)
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(where, tmp_path):
    """The real script on a CPU backend, from the repo and from a directory
    holding nothing but the script: non-zero exit, no result line."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    p = _run(cwd)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    if where == "repo":
        assert "platform 'cpu'" in p.stderr


def test_compile_cache_goes_where_env_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there and
    nowhere else; unset, the helper names the checkout's fixed directory."""
    script = (
        "import sys; sys.path.insert(0, 'src')\n"
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache as cc\n"
        "path = cc.setup_compile_cache()\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
        "print(path)\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(cc.DEFAULT_DIR)\n")
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    path, configured, default = p.stdout.split()
    assert path == configured == str(cache)
    assert any(cache.iterdir())
    assert default == os.path.join(ROOT, ".jax_cache")
