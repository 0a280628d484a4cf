"""The seeded generator: determinism, statistics, and the stated chance of
a query with no LSH candidate."""

import json
import os

import numpy as np
import pytest

from bench import gen, manifest
from bench.tests.tiny import ROOT

SEED = 2 ** 33 + 17          # beyond 32 bits: --seed takes any size


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _jaccard(a, b):
    a, b = set(a[a >= 0].tolist()), set(b[b >= 0].tolist())
    return len(a & b) / len(a | b)


@pytest.fixture(scope="module")
def corpora():
    out = {}
    for name in ("search-k256", "dedup-k128"):
        cfg = _cfg(name)
        ks = gen.keys(SEED)
        c = gen.Corpus(cfg["corpus"], cfg["d"], ks["corpus"], 3000)
        src = np.arange(400)
        out[name] = (cfg, c, src, c.queries(ks["queries"], src, 2))
    return out


def test_same_seed_same_inputs(corpora):
    cfg, c, src, q = corpora["dedup-k128"]
    ks = gen.keys(SEED)
    again = gen.Corpus(cfg["corpus"], cfg["d"], ks["corpus"], 3000)
    assert np.array_equal(again.idx, c.idx)
    assert np.array_equal(again.queries(ks["queries"], src, 2), q)
    other = gen.Corpus(cfg["corpus"], cfg["d"], gen.keys(SEED + 1)["corpus"],
                       3000)
    assert not np.array_equal(other.idx, c.idx)
    assert gen.keys(SEED)["program"] == ks["program"] < 2 ** 31
    with pytest.raises(ValueError):
        gen.keys(-1)


def test_rows_are_padded_sorted_unique_shingles(corpora):
    for cfg, c, _, q in corpora.values():
        for rows in (c.idx, q):
            assert rows.shape[1] == cfg["corpus"]["nnz"] == 256
            assert rows.dtype == np.int32
            valid = rows >= 0
            # valid entries first, strictly increasing, inside [0, d)
            assert (valid[:, :-1] >= valid[:, 1:]).all()
            assert (rows < cfg["d"]).all()
            inc = np.diff(rows, axis=1) > 0
            assert (inc | ~valid[:, 1:]).all()
            n = cfg["corpus"]["doc_len"] - cfg["corpus"]["shingle_n"] + 1
            nnz = valid.sum(axis=1)
            assert nnz.max() <= n and nnz.mean() > 0.9 * n


def test_duplicate_clusters_follow_the_corpus_statistics(corpora):
    cfg, c, _, _ = corpora["search-k256"]
    # near-copies share most shingles; unrelated documents almost none
    rows = c.idx[:600]
    sets = [set(r[r >= 0].tolist()) for r in rows]
    near = 0
    for i in range(len(sets)):
        for j in range(i + 1, len(sets)):
            inter = len(sets[i] & sets[j])
            if inter > 50:
                near += 1
    # 30% of documents in clusters of 3: about 0.3 * 600 * (600/3000) pairs
    assert 5 <= near <= 80


def test_zipf_table_keeps_the_folded_pmf():
    t = gen.zipf_table(50_000, 1.2)
    assert len(t) == 1 << gen.TABLE_BITS
    p = np.bincount(t, minlength=50_000) / len(t)
    assert abs(p[2] - 0.17884) < 1e-4          # rank 1: 1/zeta(1.2)
    assert abs(p[3] - 0.07785) < 1e-4
    assert p[:2].sum() == 0 and p[2:].min() > 0


def test_arrivals_are_the_same_set_per_seed_in_another_order():
    ph = [{"rate_qps": 1000, "ms": 1000}]
    a = gen.arrival_offsets(ph, 3.0, 1)
    b = gen.arrival_offsets(ph, 3.0, 2)
    assert len(a) == len(b) == 3000
    assert np.array_equal(a, gen.arrival_offsets(ph, 3.0, 1))
    assert not np.array_equal(a, b)
    assert (np.diff(a) >= 0).all() and a[0] == 0 and a[-1] < 3.0
    qs = (10, 50, 90)
    assert np.allclose(np.percentile(np.diff(a[:1000]), qs),
                       np.percentile(np.diff(b[:1000]), qs), rtol=0.02)
    burst = gen.arrival_offsets([{"rate_qps": 2000, "ms": 200},
                                 {"rate_qps": 100, "ms": 800}], 2.0, 3)
    assert len(burst) == 2 * (400 + 80)
    assert ((burst % 1.0) < 0.2 - 1e-9).sum() == 800


def no_candidate_p(j: float, rows: int, bands: int) -> float:
    return (1.0 - j ** rows) ** bands


def test_query_mix_stated_no_candidate_probability(corpora):
    """Each query cell's queries keep a Jaccard with their source high
    enough that 1 - (1 - J^r)^b puts a query with no LSH candidate below
    1e-6, at the lowest J generated."""
    man = manifest.load(ROOT)
    for w in man["workloads"]:
        c = manifest.cell(ROOT, w["name"])
        if c.traffic["kind"] != "query_stream":
            continue
        cfg, corpus, src, q = corpora[w["config"]]
        assert c.traffic["query_edit_tokens"] == 2
        js = np.asarray([_jaccard(q[i], corpus.idx[s])
                         for i, s in enumerate(src)])
        p = no_candidate_p(js.min(), cfg["rows_per_band"], cfg["n_bands"])
        assert p < 1e-6, (w["name"], js.min(), p)
