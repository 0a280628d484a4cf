"""b-bit packed signature buffer (SketchStore storage layer).

Signatures are stored columnar: ``words`` has shape ``(n_words, capacity)``
uint32, word-lane major, so each of the ``ceil(K / (32/b))`` packed word lanes
is contiguous across items.  The array is host-authoritative (in-place numpy
appends, O(1) amortized with capacity doubling); ``gather`` hands row-major
packed blocks to the jit'd scoring ops, which stage them on device per call.
``save``/``load`` snapshot to ``.npz``.

b-bit packing (Li & Koenig, 2011) cuts signature storage 32/b x versus raw
int32 rows — the difference between an index that fits in HBM and one that
does not at 10^8+ items.  b = 32 stores the exact signatures (bitcast).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

from ._growth import grown

_MIN_CAPACITY = 8


@dataclasses.dataclass(frozen=True)
class PackedConfig:
    k: int                      # codes per signature
    b: int = 32                 # bits per stored code (1,2,4,8,16,32)
    capacity: int = 1024        # initial item capacity

    def __post_init__(self):
        if self.b not in ops.PACK_BITS:
            raise ValueError(f"b must be one of {ops.PACK_BITS} (got {self.b})")
        if self.k <= 0:
            raise ValueError("k must be positive")

    @property
    def codes_per_word(self) -> int:
        return 32 // self.b

    @property
    def n_words(self) -> int:
        return -(-self.k // self.codes_per_word)


class PackedSignatureBuffer:
    """Append-only packed store for (N, K) int32 signatures.

    The authoritative word array lives host-side (numpy) so appends are
    in-place O(batch); ``gather``/``all_packed`` hand rows to the jit'd
    scoring ops, which stage them onto the device per call.  (An eager jnp
    buffer would copy the entire capacity on every ``.at[].set`` append —
    quadratic ingestion.)"""

    def __init__(self, cfg: PackedConfig):
        self.cfg = cfg
        cap = max(_MIN_CAPACITY, cfg.capacity)
        self._words = np.zeros((cfg.n_words, cap), np.uint32)
        self._size = 0
        # mutation counter gating the resident device copy (device_words);
        # same pattern as BandedLSHTable.device_records
        self._version = 0
        self._device: tuple[int, jnp.ndarray] | None = None
        self.device = None      # where device_words lives (None: default)

    # -- sizing ------------------------------------------------------------
    @property
    def size(self) -> int:
        return self._size

    @property
    def capacity(self) -> int:
        return self._words.shape[1]

    @property
    def nbytes(self) -> int:
        """Packed bytes actually holding data (the 32/b storage win)."""
        return self.cfg.n_words * self._size * 4

    def _grow_to(self, need: int) -> None:
        self._words = grown(self._words, need, axis=1)

    # -- writes ------------------------------------------------------------
    def append(self, sigs) -> np.ndarray:
        """Pack and append a (B, K) int32 signature batch; returns new ids."""
        sigs = jnp.asarray(sigs, jnp.int32)
        if sigs.ndim != 2 or sigs.shape[1] != self.cfg.k:
            raise ValueError(f"expected (B, {self.cfg.k}), got {sigs.shape}")
        b = sigs.shape[0]
        self._grow_to(self._size + b)
        packed = np.asarray(ops.pack_codes(sigs, self.cfg.b))  # (B, W)
        self._words[:, self._size: self._size + b] = packed.T
        ids = np.arange(self._size, self._size + b, dtype=np.int64)
        self._size += b
        self._version += 1
        return ids

    def append_packed(self, words) -> np.ndarray:
        """Append an already-packed (B, W) uint32 word batch (the fused
        sign->pack ingest path: no (B, K) int32 ever exists host-side);
        returns new ids.  Bit-identical storage to ``append(sigs)`` when
        ``words == pack_codes(sigs, b)``."""
        words = np.asarray(words, np.uint32)
        if words.ndim != 2 or words.shape[1] != self.cfg.n_words:
            raise ValueError(
                f"expected (B, {self.cfg.n_words}) packed words, "
                f"got {words.shape}")
        b = words.shape[0]
        self._grow_to(self._size + b)
        self._words[:, self._size: self._size + b] = words.T
        ids = np.arange(self._size, self._size + b, dtype=np.int64)
        self._size += b
        self._version += 1
        return ids

    # -- reads -------------------------------------------------------------
    def gather(self, ids) -> np.ndarray:
        """(C,) ids -> (C, W) uint32 packed rows for the scoring kernel."""
        ids = np.asarray(ids, np.int64)
        return np.ascontiguousarray(self._words[:, ids].T)

    def all_packed(self) -> np.ndarray:
        """(size, W) packed rows for every stored item."""
        return np.ascontiguousarray(self._words[:, : self._size].T)

    def device_words(self) -> jnp.ndarray:
        """(size, W) packed rows resident on device, re-uploaded only after
        a mutation (the fused query path scores every query batch against
        this one cached copy instead of gathering + staging per call).  It
        lives on ``self.device`` (the default device when None)."""
        if self._device is None or self._device[0] != self._version:
            self._device = None               # free the stale copy first
            self._device = (self._version,
                            jax.device_put(self.all_packed(), self.device))
        return self._device[1]

    def codes(self, ids) -> jnp.ndarray:
        """(C,) ids -> (C, K) int32 unpacked b-bit codes."""
        return ops.unpack_codes(jnp.asarray(self.gather(ids)),
                                self.cfg.k, self.cfg.b)

    # -- snapshots ---------------------------------------------------------
    @classmethod
    def from_rows(cls, cfg: PackedConfig, rows) -> "PackedSignatureBuffer":
        """Rebuild a buffer from (N, W) row-major packed words (the
        ``gather``/``all_packed`` layout — what snapshots store)."""
        rows = np.asarray(rows, np.uint32)
        n = rows.shape[0]
        buf = cls(cfg)
        buf._grow_to(n)
        buf._words[:, :n] = rows.T
        buf._size = n
        buf._version += 1
        return buf

    def save(self, path: str) -> None:
        np.savez(path, words=self.all_packed(), k=self.cfg.k, b=self.cfg.b)

    @classmethod
    def load(cls, path: str) -> "PackedSignatureBuffer":
        with np.load(path) as z:
            words = z["words"]                         # (N, W) rows
            cfg = PackedConfig(k=int(z["k"]), b=int(z["b"]),
                               capacity=max(_MIN_CAPACITY, len(words)))
        return cls.from_rows(cfg, words)
