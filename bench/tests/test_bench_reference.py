"""The reference against the definitions written out longhand: C-MinHash
by its formula, LSH candidates by dict buckets, exact ranking."""

from collections import defaultdict

import numpy as np

from bench.reference import Reference, count_wrong, permutations

D, K, B, R = 1024, 32, 8, 4


def _rows(rng, n, nnz=24):
    out = np.full((n, nnz), -1, np.int32)
    for i in range(n):
        s = np.sort(rng.choice(D, rng.integers(8, nnz + 1), replace=False))
        out[i, :len(s)] = s
    return out


def _longhand_sig(row, sigma, pi):
    s = row[row >= 0]
    return np.asarray([min(pi[(sigma[j] - c - 1) % D] for j in s)
                       for c in range(K)], np.int32)


def _longhand_topk(docs, queries, top_k):
    buckets = defaultdict(list)
    for i, sig in enumerate(docs):
        for b in range(B):
            buckets[(b, tuple(sig[b * R:(b + 1) * R]))].append(i)
    ids = np.full((len(queries), top_k), -1, np.int64)
    scores = np.zeros((len(queries), top_k), np.float32)
    for qi, q in enumerate(queries):
        cand = {i for b in range(B)
                for i in buckets.get((b, tuple(q[b * R:(b + 1) * R])), [])}
        pool = sorted(cand) if cand else range(len(docs))
        ranked = sorted(pool, key=lambda i: (-(docs[i] == q).sum(), i))
        for r, i in enumerate(ranked[:top_k]):
            ids[qi, r] = i
            scores[qi, r] = np.float32((docs[i] == q).sum()) / np.float32(K)
    return ids, scores


def test_reference_matches_the_longhand_definitions():
    rng = np.random.default_rng(5)
    docs = _rows(rng, 300)
    # near-duplicate queries (one shingle swapped), plus two with no
    # candidate: fresh random rows
    queries = docs[rng.integers(0, 300, 40)].copy()
    queries[:, 0] = rng.integers(0, D, 40)
    queries = np.concatenate([queries, _rows(rng, 2)])
    ref = Reference(d=D, k=K, n_bands=B, rows_per_band=R, seed=77)
    sigma, pi = permutations(77, D)
    dsig = np.asarray(ref.signatures(docs, block=64))
    qsig = np.asarray(ref.signatures(queries, block=64))
    for i in (0, 1, 299):
        assert np.array_equal(dsig[i], _longhand_sig(docs[i], sigma, pi))
    want = _longhand_topk(dsig, qsig, 10)
    got = ref.topk(ref.signatures(docs, block=64),
                   ref.signatures(queries, block=64), 10)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])
    assert count_wrong(*got, *want) == 0
    bumped = got[1].copy()
    bumped[3, 0] += np.float32(1 / K)
    assert count_wrong(got[0], bumped, *want) == 1
    assert list(ref.no_candidate) == [40, 41]
