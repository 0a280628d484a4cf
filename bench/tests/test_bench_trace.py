"""The trace reduction: busy and idle share, per-program device time with
nested ops counted once, and idle gaps named by the host annotation."""

import os

import pytest

from bench import trace
from bench.tests.tiny import ROOT

RECORDED = os.path.join(ROOT, "bench", "tests", "data",
                        "lookup_v5e.xplane.pb.gz")


def _events():
    ms = 1_000_000
    ev = trace.Events()
    ev.host = [("main", "bench.window", 0, 100 * ms),
               ("coalescer", "bench.query", 10 * ms, 50 * ms),
               ("coalescer", "bench.fold", 20 * ms, 30 * ms),
               ("coalescer", "bench.drain", 60 * ms, 95 * ms)]
    d = "/device:TPU:0"
    ev.device = [
        # outside the window on the left: clipped away
        (d, "jit_sign", "fusion.1", -5 * ms, 5 * ms),
        (d, "jit_lsh_probe_jnp", "while.3", 30 * ms, 40 * ms),
        (d, "jit_lsh_probe_jnp", "fusion.6", 32 * ms, 38 * ms),   # nested
        (d, "jit_score_topk", "sort.1", 40 * ms, 45 * ms),
        (d, "jit_score_topk", "fusion.2", 90 * ms, 110 * ms),
    ]
    return ev


def test_busy_idle_and_program_time():
    red = trace.reduce(_events())
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.100)
    # busy: [0,5] + [30,45] + [90,100] = 30 ms
    assert red["busy_s"] == pytest.approx(0.030)
    assert red["program_s"]["jit_lsh_probe_jnp"] == pytest.approx(0.010)
    assert trace.program_seconds(red, "jit_lsh_probe") == pytest.approx(0.010)
    assert trace.program_seconds(red, "jit_nothing") is None
    ops = dict(red["device_ops"])
    assert ops["jit_score_topk/fusion.2"] == pytest.approx(0.010)
    assert ops["jit_lsh_probe_jnp/fusion.6"] == pytest.approx(0.006)


def test_idle_gaps_named_by_what_the_host_did():
    red = trace.reduce(_events())
    gaps = [(name, round(s * 1e3, 6)) for name, s in red["idle_gaps"]]
    # gaps: [5,30] query+fold (query covers 20 of 25 ms),
    # [45,90] drain covers 30 of 45 ms
    assert gaps == [("bench.drain", 45.0), ("bench.query", 25.0)]


def test_innermost_annotation_wins_a_tie():
    ms = 1_000_000
    ev = trace.Events()
    ev.host = [("main", "bench.window", 0, 10 * ms),
               ("t", "bench.query", 0, 10 * ms),
               ("t", "bench.partial", 2 * ms, 8 * ms)]
    ev.device = [("/device:TPU:0", "p", "op", 0, 2 * ms),
                 ("/device:TPU:0", "p", "op", 8 * ms, 10 * ms)]
    assert trace.reduce(ev)["idle_gaps"] == [["bench.partial", 0.006]]


def test_no_window_annotation_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(trace.Events())


def test_recorded_chip_trace():
    """A 0.33 s traced window of a small lookup run on one v5e: the
    ``XLA Ops`` of its device plane, the ``bench.*`` annotations of its
    host threads."""
    ev = trace.load(RECORDED)
    assert {p for p, *_ in ev.device} == {"/device:TPU:0"}
    red = trace.reduce(ev)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.326811867, abs=1e-12)
    assert red["busy_s"] == pytest.approx(0.006697463, abs=1e-12)
    # the union of every program's time is the busy time, each at most once
    assert sum(red["program_s"].values()) == pytest.approx(red["busy_s"],
                                                           rel=1e-9)
    assert trace.program_seconds(red, "jit_lsh_probe_jnp") == pytest.approx(
        0.001469029, abs=1e-12)
    assert trace.program_seconds(red, "jit_cminhash_sparse") == \
        pytest.approx(0.003533008, abs=1e-12)
    top = red["device_ops"][0]
    assert top[0] == "jit_cminhash_sparse_pallas/cminhash_sparse_pallas.1 " \
                     "s32[8,1,128]"
    assert len(red["idle_gaps"]) == 10
    assert {name for name, _ in red["idle_gaps"]} <= {
        "bench.query", "bench.sign", "bench.drain", "bench.fold",
        "bench.partial", "host.other"}
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in red["idle_gaps"]) < idle


def test_op_names_drop_layout_and_body():
    assert trace.op_name("%fusion.6 = s32[512,10]{0,1:T(8,128)S(1)} "
                         "fusion(s32[33554432,10]{0,1:T(8,128)} %x)") == \
        "fusion.6 s32[512,10]"
    assert trace.op_name("custom op text") == "custom op text"
