"""Seeded corpus, query and arrival generator, vectorised on the device.

The statistics are those of ``repro.data.synthetic.corpus_with_duplicates``
(what ``chip_smoke.make_corpus`` feeds the served path): Zipf(alpha) token
ranks folded into ``[2, vocab)``, fixed-length documents, a share of the
documents in clusters of near-copies of one base document with a fraction
of their tokens resampled, the rest unique, all in random order.  Tokens
become n-gram shingles hashed into a ``d``-slot universe (``d`` a power of
two) and each document becomes one padded row of sorted unique shingle
indices, ``-1`` after the last: the ``(n, nnz)`` int32 rows the served
path signs.

Where the reference generator loops over documents in Python (70 s for
2^20 documents), this one draws every token with one table lookup on the
device: the folded Zipf pmf is exact (a Hurwitz zeta per token) and is
quantised into a ``2^TABLE_BITS``-entry inverse-CDF table, so each token
probability is kept to within ``2^-TABLE_BITS``.  The shingle hash is this
module's own (a polynomial over uint32 with a murmur finaliser); any
well-mixed hash gives the same set statistics.

Everything is a pure function of the seed: ``keys(seed)`` expands any
non-negative integer into the device keys and the program's permutation
seed, so the same seed gives the same documents, queries and arrivals.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy import special

TABLE_BITS = 24
_MUL = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1,
        0xD3A2646C, 0xFD7046C5, 0xB55A4F09)


def keys(seed: int) -> dict:
    """Expand ``--seed`` into independent streams: device keys for the
    corpus and the queries, a numpy seed for the arrivals and samples, and
    the 31-bit seed the program draws its two permutations from."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0 (got {seed})")
    w = np.random.SeedSequence(seed).generate_state(8, dtype=np.uint32)
    wrap = lambda a, b: jax.random.wrap_key_data(  # noqa: E731
        np.asarray([a, b], np.uint32), impl="threefry2x32")
    return {"corpus": wrap(w[0], w[1]), "queries": wrap(w[2], w[3]),
            "numpy": int(w[6]), "program": int(w[7] >> 1)}


def zipf_table(vocab: int, alpha: float) -> np.ndarray:
    """(2^TABLE_BITS,) int32 inverse-CDF table of the token distribution:
    rank r ~ Zipf(alpha) mapped to token 2 + (r - 1) mod (vocab - 2).

    The folded pmf of token 2 + m - 1 is sum_j (m + j v)^-alpha / zeta(alpha)
    = v^-alpha zeta(alpha, m / v) / zeta(alpha), v = vocab - 2."""
    v = vocab - 2
    m = np.arange(1, v + 1, dtype=np.float64)
    pmf = v ** -alpha * special.zeta(alpha, m / v) / special.zeta(alpha)
    size = 1 << TABLE_BITS
    edges = np.round(np.cumsum(pmf) / pmf.sum() * size).astype(np.int64)
    edges[-1] = size
    counts = np.diff(np.r_[0, edges])
    return np.repeat(np.arange(2, vocab, dtype=np.int32), counts)


def _draw(key, shape, table):
    bits = jax.random.bits(key, shape, jnp.uint32)
    return table[(bits >> (32 - TABLE_BITS)).astype(jnp.int32)]


def _mix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def shingle_rows(tokens, *, n: int, d: int, nnz: int):
    """(B, L) int32 tokens -> (B, nnz) int32 sorted unique shingle indices
    in [0, d), -1 padded."""
    if d & (d - 1) or not 2 <= d <= 1 << 31:
        raise ValueError(f"d must be a power of two (got {d})")
    b, length = tokens.shape
    m = length - n + 1
    if not 0 < m <= nnz:
        raise ValueError(f"{m} shingles do not fit {nnz} columns")
    t = tokens.astype(jnp.uint32)
    h = jnp.zeros((b, m), jnp.uint32)
    for i in range(n):
        h = (h + t[:, i: i + m]) * jnp.uint32(_MUL[i % len(_MUL)])
    idx = (_mix32(h) >> (32 - d.bit_length() + 1)).astype(jnp.int32)
    s = jnp.sort(idx, axis=1)
    dup = jnp.concatenate([jnp.zeros((b, 1), bool), s[:, 1:] == s[:, :-1]],
                          axis=1)
    big = jnp.int32(np.iinfo(np.int32).max)
    s = jnp.sort(jnp.where(dup, big, s), axis=1)
    s = jnp.where(s == big, -1, s)
    return jnp.pad(s, ((0, 0), (0, nnz - m)), constant_values=-1)


def _edit(key, tokens, n_edit: int, table):
    """Resample ``n_edit`` distinct positions of each row."""
    if not n_edit:
        return tokens
    k_pos, k_tok = jax.random.split(key)
    u = jax.random.uniform(k_pos, tokens.shape)
    pos = jax.lax.top_k(u, n_edit)[1]
    new = _draw(k_tok, pos.shape, table)
    rows = jnp.arange(tokens.shape[0])[:, None]
    return tokens.at[rows, pos].set(new)


@functools.partial(jax.jit, static_argnames=("shape",))
def _draw_jit(key, table, *, shape):
    return _draw(key, shape, table)


@functools.partial(jax.jit, static_argnames=(
    "n_clusters", "n_clustered", "cluster_size", "n_edit"))
def _near_copies(key, bases, table, *, n_clusters: int, n_clustered: int,
                 cluster_size: int, n_edit: int):
    near = jnp.repeat(bases[:n_clusters], cluster_size, axis=0)
    return _edit(key, near[:n_clustered], n_edit, table)


@functools.partial(jax.jit, static_argnames=("n_clusters",))
def _assemble(near, bases, *, n_clusters: int):
    return jnp.concatenate([near, bases[n_clusters:]])


def corpus_tokens(key, table, *, n_docs: int, doc_len: int,
                  dup_fraction: float, cluster_size: int, cluster_edits: int):
    """(tokens, order): (n_docs, doc_len) int32 device tokens, clusters of
    ``cluster_size`` near-copies (each a base with ``cluster_edits`` tokens
    resampled) for ``dup_fraction`` of the documents and unique documents
    after them, and the (n_docs,) host permutation that puts them in random
    order: document i of the corpus is ``tokens[order[i]]``.

    The steps are separate programs, each freeing what it no longer needs,
    so the device holds about two corpus-sized arrays at a time, where one
    program holding every step's temporaries peaked at three."""
    n_clusters = max(int(n_docs * dup_fraction) // cluster_size, 1)
    n_clustered = min(n_clusters * cluster_size, n_docs)
    k_base, k_edit, k_perm = jax.random.split(key, 3)
    bases = _draw_jit(k_base, table, shape=(
        n_clusters + n_docs - n_clustered, doc_len))
    near = _near_copies(k_edit, bases, table, n_clusters=n_clusters,
                        n_clustered=n_clustered, cluster_size=cluster_size,
                        n_edit=cluster_edits)
    tokens = _assemble(near, bases, n_clusters=n_clusters)
    del bases, near
    order = np.asarray(jax.random.permutation(k_perm, n_docs))
    return tokens, order


@functools.partial(jax.jit, static_argnames=("n", "d", "nnz"))
def _shingle_jit(tokens, *, n: int, d: int, nnz: int):
    return shingle_rows(tokens, n=n, d=d, nnz=nnz)


@functools.partial(jax.jit, static_argnames=("n_edit", "n", "d", "nnz"))
def _queries_jit(key, rows, table, *, n_edit: int, n: int, d: int, nnz: int):
    return shingle_rows(_edit(key, rows, n_edit, table), n=n, d=d, nnz=nnz)


class Corpus:
    """A seeded document set and the near-duplicate queries drawn from it.

    ``cfg`` is a configuration file's ``corpus`` group: vocab, zipf_alpha,
    doc_len, dup_fraction, cluster_size, cluster_edit_fraction, shingle_n,
    nnz, with the universe size ``d`` beside it.  The tokens stay in
    generation order (on the device, or on the host with ``keep_tokens``);
    ``order`` maps a corpus position to its row there."""

    def __init__(self, cfg: dict, d: int, key, n_docs: int, *,
                 keep_tokens: bool = False, block: int = 1 << 18):
        self.cfg, self.d = cfg, d
        self.table = jnp.asarray(zipf_table(cfg["vocab"], cfg["zipf_alpha"]))
        self.tokens, self.order = corpus_tokens(
            key, self.table, n_docs=n_docs, doc_len=cfg["doc_len"],
            dup_fraction=cfg["dup_fraction"],
            cluster_size=cfg["cluster_size"],
            cluster_edits=int(cfg["doc_len"] * cfg["cluster_edit_fraction"]))
        # shingle in blocks (the sorts' temporaries stay a fraction of HBM)
        # and put the rows in corpus order on the host
        parts = [np.asarray(_shingle_jit(
                     self.tokens[lo: lo + block], n=cfg["shingle_n"], d=d,
                     nnz=cfg["nnz"]))
                 for lo in range(0, n_docs, block)]
        self.idx = np.concatenate(parts)[self.order]
        if keep_tokens:
            self.tokens = np.asarray(self.tokens)

    def queries(self, key, src: np.ndarray, n_edit: int) -> np.ndarray:
        """(len(src), nnz) rows: document ``src[i]`` with ``n_edit`` tokens
        resampled, then shingled."""
        at = self.order[np.asarray(src)]
        if isinstance(self.tokens, np.ndarray):
            rows = jnp.asarray(self.tokens[at])
        else:
            rows = self.tokens[jnp.asarray(at)]
        out = _queries_jit(key, rows, self.table, n_edit=n_edit,
                           n=self.cfg["shingle_n"], d=self.d,
                           nnz=self.cfg["nnz"])
        return np.asarray(out)


def arrival_offsets(phases: list[dict], seconds: float,
                    seed: int) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop schedule.

    ``phases`` repeat in turn until ``seconds`` is filled; each is
    ``{"rate_qps": r, "ms": t}``.  A phase of length t at rate r carries
    round(r t) arrivals whose gaps are the exponential distribution's
    quantiles at (i + 1/2)/n, shuffled by the seed: Poisson-like spacing,
    with every seed offering the same number of queries and the same set of
    gaps in another order."""
    rng = np.random.default_rng(seed)
    out, t0 = [], 0.0
    while t0 < seconds - 1e-12:
        for ph in phases:
            span = min(ph["ms"] / 1e3, seconds - t0)
            n = int(round(ph["rate_qps"] * span))
            if n:
                gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
                gaps = rng.permutation(gaps)
                out.append(t0 + (np.cumsum(gaps) - gaps) * span / gaps.sum())
            t0 += span
            if t0 >= seconds - 1e-12:
                break
    return np.concatenate(out) if out else np.zeros(0)
