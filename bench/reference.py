"""The plain reference the benchmark's answers are compared with.

It imports nothing of the program and takes nothing the program made: it
draws the configuration's two permutations from the seed itself, signs the
benchmark's own shingle rows by the definition of C-MinHash, finds LSH
candidates by joining band rows, and scores and ranks them exactly.

* Permutations: the deployment's hash family is (sigma, pi) =
  ``jax.random.permutation`` of [0, d) under the two halves of
  ``jax.random.split(PRNGKey(seed))``.
* Signing: h_c(S) = min over j in S of pi[(sigma[j] - c - 1) mod d] for
  c = 0..K-1.  Row t of the window table holds pi[(t - 1 - c) mod d] over
  c, so a document's signature is the column-wise min of the table rows of
  its sigma-mapped shingles.
* Candidates: a document is a candidate of a query when all
  ``rows_per_band`` codes of at least one band are equal.  Each band's
  rows get a 32-bit hash of this module's own; the documents' hashes are
  sorted per band and each query's hash is looked up (a sort-merge join),
  and every joined pair is then checked code for code, so a hash collision
  never makes a candidate.
* Answer: candidates ranked by (equal codes desc, id asc), the first
  ``top_k`` kept, score = equal codes / K in float32; padding is id -1 and
  score 0.  A query with no candidate is answered over every document.

Signing, hashing, sorting and lookups run on the device in whole-array
calls; the pairs are expanded, checked and ranked on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_INT_MAX = np.iinfo(np.int32).max


def permutations(seed: int, d: int) -> tuple[np.ndarray, np.ndarray]:
    k_sigma, k_pi = jax.random.split(jax.random.PRNGKey(seed))
    return (np.asarray(jax.random.permutation(k_sigma, d), np.int32),
            np.asarray(jax.random.permutation(k_pi, d), np.int32))


@functools.partial(jax.jit, static_argnames=("block",))
def _sign(idx, sigma, table, *, block: int):
    def one(rows):
        valid = rows >= 0
        t = sigma[jnp.where(valid, rows, 0)]
        vals = table[t]                                    # (B, nnz, K)
        return jnp.where(valid[..., None], vals, _INT_MAX).min(axis=1)
    n, nnz = idx.shape
    out = jax.lax.map(one, idx.reshape(n // block, block, nnz))
    return out.reshape(n, -1)


@functools.partial(jax.jit, static_argnames=("n_bands",))
def _band_hash(sigs, *, n_bands: int):
    """(N, K) int32 -> (N, n_bands) uint32: FNV-style fold of each band."""
    rows = sigs.reshape(sigs.shape[0], n_bands, -1).astype(jnp.uint32)
    h = jnp.full(rows.shape[:2], 0x811C9DC5, jnp.uint32)
    for c in range(rows.shape[2]):
        h = (h ^ rows[:, :, c]) * jnp.uint32(0x01000193)
        h = h ^ (h >> 15)
    return h


@jax.jit
def _sorted_bands(keys):
    """(N, B) hashes -> per band (B, N) sorted hashes and their row ids."""
    kt = keys.T
    ids = jnp.broadcast_to(jnp.arange(kt.shape[1], dtype=jnp.int32), kt.shape)
    return jax.lax.sort((kt, ids), dimension=1, num_keys=1)


@jax.jit
def _lookup(sorted_keys, qkeys):
    """(B, N) sorted, (Q, B) query hashes -> (B, Q) first match, count."""
    left = jax.vmap(lambda a, v: jnp.searchsorted(a, v, side="left"))
    right = jax.vmap(lambda a, v: jnp.searchsorted(a, v, side="right"))
    lo = left(sorted_keys, qkeys.T)
    return lo, right(sorted_keys, qkeys.T) - lo


@jax.jit
def _brute(doc_sigs, q):
    """Every document's equal-code count against one query, ranked."""
    counts = (doc_sigs == q[None, :]).sum(axis=1, dtype=jnp.int32)
    ids = jnp.arange(doc_sigs.shape[0], dtype=jnp.int32)
    neg, ids = jax.lax.sort((-counts, ids), num_keys=2)
    return -neg, ids


class Reference:
    def __init__(self, *, d: int, k: int, n_bands: int, rows_per_band: int,
                 seed: int):
        if n_bands * rows_per_band != k:
            raise ValueError("n_bands * rows_per_band must equal k")
        self.d, self.k = d, k
        self.n_bands, self.r = n_bands, rows_per_band
        # queries of the last ``topk`` with no LSH candidate: the rows the
        # served path must also answer by brute force
        self.no_candidate = np.zeros(0, np.int64)
        sigma, pi = permutations(seed, d)
        cols = (np.arange(d)[:, None] - 1 - np.arange(k)[None, :]) % d
        self._sigma = jnp.asarray(sigma)
        self._table = jnp.asarray(pi[cols])                # (d, K) int32

    def signatures(self, idx: np.ndarray, block: int = 1024) -> jax.Array:
        """(N, nnz) padded shingle rows -> (N, K) int32 signatures, on the
        device."""
        n = len(idx)
        pad = -n % block
        if pad:
            idx = np.concatenate([idx, np.repeat(idx[:1], pad, axis=0)])
        out = _sign(jnp.asarray(idx), self._sigma, self._table, block=block)
        return out[:n]

    def candidates(self, doc_sigs: jax.Array, q_sigs: jax.Array):
        """Unique (query, doc) pairs with one equal band, as (query index,
        index into ``docs``, ``docs`` the distinct doc ids, their signature
        rows, the query signatures), all on the host."""
        skeys, order = _sorted_bands(_band_hash(doc_sigs, n_bands=self.n_bands))
        lo, cnt = _lookup(skeys, _band_hash(q_sigs, n_bands=self.n_bands))
        lo, cnt, order = np.asarray(lo), np.asarray(cnt), np.asarray(order)
        b_of, q_of = np.nonzero(cnt)                        # (band, query)
        c = cnt[b_of, q_of]
        start = np.repeat(lo[b_of, q_of] - (np.cumsum(c) - c), c)
        pos = start + np.arange(c.sum())
        band = np.repeat(b_of, c)
        qi = np.repeat(q_of, c).astype(np.int64)
        di = order[band, pos].astype(np.int64)
        uniq, inv = np.unique(di, return_inverse=True)
        rows = np.asarray(doc_sigs[jnp.asarray(uniq)]) if len(uniq) else \
            np.zeros((0, self.k), np.int32)
        qh = np.asarray(q_sigs)
        # keep a joined pair only when its band's codes are all equal
        cols = band[:, None] * self.r + np.arange(self.r)[None, :]
        same = (rows[inv[:, None], cols] == qh[qi[:, None], cols]).all(axis=1)
        n_u = max(len(uniq), 1)
        key = np.unique(qi[same] * n_u + inv[same])
        return key // n_u, key % n_u, uniq, rows, qh

    def topk(self, doc_sigs: jax.Array, q_sigs: jax.Array, top_k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """The reference answers: ids (Q, top_k) int64, scores float32."""
        qi, ui, uniq, rows, qh = self.candidates(doc_sigs, q_sigs)
        q = len(qh)
        counts = (rows[ui] == qh[qi]).sum(axis=1)
        di = uniq[ui]
        ids = np.full((q, top_k), -1, np.int64)
        scores = np.zeros((q, top_k), np.float32)
        order = np.lexsort((di, -counts, qi))
        qs, ds, cs = qi[order], di[order], counts[order]
        first = np.r_[0, np.flatnonzero(qs[1:] != qs[:-1]) + 1]
        rank = np.arange(len(qs)) - np.repeat(
            first, np.diff(np.r_[first, len(qs)]))
        keep = rank < top_k
        ids[qs[keep], rank[keep]] = ds[keep]
        scores[qs[keep], rank[keep]] = (cs[keep].astype(np.float32)
                                        / np.float32(self.k))
        self.no_candidate = np.setdiff1d(np.arange(q), qs)
        for row in self.no_candidate:                  # answered by brute
            c, o = (np.asarray(a[:top_k])
                    for a in _brute(doc_sigs, jnp.asarray(qh[row])))
            ids[row, :len(o)] = o
            scores[row, :len(o)] = c.astype(np.float32) / np.float32(self.k)
        return ids, scores


def count_wrong(ids: np.ndarray, scores: np.ndarray, ref_ids: np.ndarray,
                ref_scores: np.ndarray) -> int:
    """Rows whose ids or scores differ from the reference's in any place."""
    return int((~((ids == ref_ids).all(axis=1)
                  & (scores == ref_scores).all(axis=1))).sum())
