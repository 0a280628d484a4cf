"""Coverage for serving helpers, loaders, and the roofline analysis layer."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import (cminhash_kernel_roofline, model_flops,
                                     report_markdown, roofline)
from repro.configs import get_config, reduced
from repro.core.engine import SketchConfig, SketchEngine
from repro.data.loader import PrefetchIterator
from repro.models import build
from repro.serve.decode import generate, sample_token


def test_generate_greedy_deterministic():
    cfg = reduced(get_config("llama3_2_1b"), d_model=64, vocab=128)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": np.asarray(rng.integers(0, 128, (3, 12)), np.int32)}
    a = generate(bundle, params, batch, max_new_tokens=6, temperature=0.0)
    b = generate(bundle, params, batch, max_new_tokens=6, temperature=0.0)
    assert a.shape == (3, 6)
    assert np.array_equal(a, b)


def test_sample_token_temperature():
    logits = jnp.asarray([[0.0, 10.0, 0.0]])
    greedy = sample_token(logits, jax.random.PRNGKey(0), 0.0)
    assert int(greedy[0]) == 1
    sampled = sample_token(logits, jax.random.PRNGKey(0), 1.0)
    assert sampled.shape == (1,)


def test_prefetch_iterator_order_and_stop():
    it = PrefetchIterator(iter(range(7)), depth=3)
    assert list(it) == list(range(7))


def test_sketch_engine_memory_accounting():
    eng = SketchEngine(SketchConfig(d=1024, k=64))
    assert eng.parameter_bytes == 2 * 1024 * 4
    assert SketchEngine.classical_parameter_bytes(1024, 64) == 64 * 1024 * 4
    eng0 = SketchEngine(SketchConfig(d=1024, k=64, use_sigma=False))
    assert eng0.parameter_bytes == 1024 * 4


def _fake_record(kind="train", flops=1e12, bytes_=1e11, coll=1e9):
    return {
        "arch": "x", "shape": "train_4k", "mesh": "single_pod",
        "n_chips": 256, "seq_len": 4096, "global_batch": 256, "kind": kind,
        "params": int(1e9), "active_params": int(1e9), "status": "ok",
        "compile_s": 1.0,
        "memory": {"argument_bytes": 1e9, "output_bytes": 1, "temp_bytes": 1,
                   "alias_bytes": 1, "code_bytes": 0},
        "xla_cost": {"flops": flops / 10, "bytes accessed": bytes_ / 10},
        "hlo_cost": {"flops": flops, "bytes": bytes_, "bytes_naive": bytes_,
                     "collective_bytes": coll, "collective_breakdown": {},
                     "n_collectives": 3},
    }


def test_roofline_terms_and_dominance():
    r = roofline(_fake_record(flops=1.97e14, bytes_=8.19e11, coll=5e10))
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(1.0)
    assert r["collective_s"] == pytest.approx(1.0)
    # model flops: train = 6 * N * tokens
    assert r["model_flops"] == pytest.approx(6 * 1e9 * 256 * 4096)
    r2 = roofline(_fake_record(bytes_=1e14))
    assert r2["dominant"] == "memory"


def test_model_flops_kinds():
    rec = _fake_record()
    assert model_flops(rec) == 6 * 1e9 * 256 * 4096
    rec["kind"] = "prefill"
    assert model_flops(rec) == 2 * 1e9 * 256 * 4096
    rec["kind"] = "decode"
    assert model_flops(rec) == 2 * 1e9 * 256


def test_report_markdown_from_dir(tmp_path):
    rec = _fake_record()
    (tmp_path / "single_pod__x__train_4k.json").write_text(json.dumps(rec))
    md = report_markdown(str(tmp_path), "single_pod")
    assert "### Roofline" in md and "| x | train_4k |" in md


def test_kernel_roofline_packing_helps_memory_only():
    a = cminhash_kernel_roofline(1024, 65536, 1024, packed=False)
    b = cminhash_kernel_roofline(1024, 65536, 1024, packed=True)
    assert a["ops"] == b["ops"]
    assert b["bytes"] < a["bytes"] / 2
    assert b["arith_intensity"] > a["arith_intensity"]


def test_roofline_peaks_keyed_by_device_kind():
    import pytest

    from repro.analysis import roofline
    assert roofline.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no peak figures"):
        roofline.peaks("cpu")
