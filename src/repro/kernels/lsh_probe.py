"""Device-resident LSH bucket probe (the last host-bound leg of a query).

``BandedLSHTable.lookup`` resolves (Q, n_bands) uint64 band hashes to
candidate rows by quadratic-probing the fused records array

    records (n_bands, n_slots, 2 + W) int32
    records[b, s, :2] = band-hash halves (-1, -1 = unused)
    records[b, s, 2:] = posting item ids (-1 padded)

The numpy loop is the CPU-tuned reference (early-terminating chains, ~1
gather per entry at sane load).  These twins run the same probe on device
over the *same* records layout: the table uploads its records once
(``BandedLSHTable.device_records``, cached by mutation version) and each
query batch is a fixed-depth branchless probe — correct without early
termination because the open-addressing invariant guarantees at most one
matching slot per (band, key) and no record ever sits past an unused slot
on its own chain (slots are never freed), so probing the full chain and
keeping the single hit reproduces the early-terminating walk exactly.

The uint64 leg (band-hash fold + ``key % n_slots``) stays on host — numpy
uint64 is exact and JAX's default int32 domain is not; ``probe_operands``
reduces each entry to five int32s (band offset, base slot, key halves,
validity) and everything after that is device work:

* ``lsh_probe_jnp``    — compiled-jnp twin: one (E, 2+W) gather per probe
  depth, hit-select folded across depths.  The device probe on every
  backend, TPU included, and the oracle-equivalent of the kernel.
* ``lsh_probe_pallas`` — Pallas kernel: grid over query-entry tiles,
  records block resident in VMEM, fori_loop of per-entry dynamic slices
  with a statically unrolled probe chain.  ``interpret=True`` runs on CPU.

Sentinel-valued hashes (the empty-slot sentinel, routed to the spill list
at insert) are masked via the validity flag — their halves (-1, -1) would
otherwise match every unused slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Array = jax.Array

# The one definition of the probe geometry: store/table.py (the numpy walk)
# imports both of these, so host and device can never disagree on the chain
# or the empty-slot sentinel.
SENTINEL_KEY = np.uint64(0xFFFFFFFFFFFFFFFF)


def probe_offset(t: int) -> int:
    """Quadratic (triangular) probe offset t(t+1)/2 — breaks the primary
    clustering that gives linear probing its heavy chain-length tail.
    Inserts, the numpy walk, and both device impls all walk this sequence.
    """
    return t * (t + 1) // 2


META_COLS = 5    # lin_band, base_slot, key_lo, key_hi, valid


def probe_operands(hashes: np.ndarray, n_slots: int) -> np.ndarray:
    """(Q, n_bands) uint64 band hashes -> (Q * n_bands, 5) int32 operands.

    The host-side uint64 leg: columns are [band * n_slots, key % n_slots,
    key_lo, key_hi, valid].  Key halves use the same native-endian int32
    view as the records array, so the in-kernel compare is bit-exact with
    the numpy path.
    """
    q, nb = hashes.shape
    key = np.ascontiguousarray(hashes.reshape(-1))
    meta = np.empty((q * nb, META_COLS), np.int32)
    meta[:, 0] = np.tile(np.arange(nb, dtype=np.int32) * n_slots, q)
    meta[:, 1] = (key % np.uint64(n_slots)).astype(np.int32)
    meta[:, 2:4] = key.view(np.int32).reshape(-1, 2)
    meta[:, 4] = (key != SENTINEL_KEY)
    return meta


def _offsets(max_probes: int) -> np.ndarray:
    """The full probe chain as an int32 vector (for the jnp fori_loop)."""
    return np.asarray([probe_offset(t) for t in range(max_probes)], np.int32)


@functools.partial(jax.jit, static_argnames=("n_slots", "max_probes"))
def lsh_probe_jnp(flat_records: Array, meta: Array, *, n_slots: int,
                  max_probes: int) -> Array:
    """Compiled-jnp probe: (E, 5) operands -> (E, W) candidate ids, -1 pad.

    ``flat_records`` is the (n_bands * n_slots, 2 + W) device records view.
    One fused-record gather per probe depth; the single possible hit per
    entry is folded in with a select, so depths can run in any order.
    """
    w = flat_records.shape[1] - 2
    lin_band, base = meta[:, 0], meta[:, 1]
    valid = meta[:, 4] != 0
    offs = jnp.asarray(_offsets(max_probes))

    def body(t, out):
        slot = (base + offs[t]) % n_slots
        rec = flat_records[lin_band + slot]                # (E, 2+W) gather
        hit = (rec[:, 0] == meta[:, 2]) & (rec[:, 1] == meta[:, 3]) & valid
        return jnp.where(hit[:, None], rec[:, 2:], out)

    out0 = jnp.full((meta.shape[0], w), -1, jnp.int32)
    return jax.lax.fori_loop(0, max_probes, body, out0)


def _probe_kernel(rec_ref, meta_ref, out_ref, *, et: int, ns: int, w: int,
                  max_probes: int):
    recs = rec_ref[...]                                    # (R, 2+W) resident
    meta = meta_ref[...]                                   # (et, 5)

    def body(e, out):
        m = jax.lax.dynamic_slice(meta, (e, 0), (1, META_COLS))
        lin, base = m[0, 0], m[0, 1]
        klo, khi, valid = m[0, 2], m[0, 3], m[0, 4] != 0
        row = jnp.full((1, w), -1, jnp.int32)
        for t in range(max_probes):                        # static chain
            slot = (base + probe_offset(t)) % ns
            rec = jax.lax.dynamic_slice(recs, (lin + slot, 0), (1, 2 + w))
            hit = (rec[0, 0] == klo) & (rec[0, 1] == khi) & valid
            row = jnp.where(hit, rec[:, 2:], row)
        return jax.lax.dynamic_update_slice(out, row, (e, 0))

    out_ref[...] = jax.lax.fori_loop(
        0, et, body, jnp.full((et, w), -1, jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("n_slots", "max_probes", "block_e", "interpret"),
)
def lsh_probe_pallas(flat_records: Array, meta: Array, *, n_slots: int,
                     max_probes: int, block_e: int = 128,
                     interpret: bool = True) -> Array:
    """Pallas probe kernel: (E, 5) operands -> (E, W) candidate ids, -1 pad.

    Grid over entry tiles of ``block_e``; the records block is VMEM-resident
    across the whole grid (4 * n_bands * n_slots * (2 + W) bytes), so
    per-tile HBM traffic is just the operand block and the output rows.
    Interpret mode only: at served sizes that block is gigabytes, and the
    per-entry value-level dynamic slices do not lower on TPU either, so
    ``kernels.dispatch`` never selects this kernel there.
    """
    e, mc = meta.shape
    r, rw = flat_records.shape
    w = rw - 2
    et = max(1, block_e)
    ne = -(-e // et)
    if ne * et != e:                  # pad with invalid entries (valid=0)
        pad = np.zeros((ne * et - e, META_COLS), np.int32)
        meta = jnp.concatenate([meta, jnp.asarray(pad)])
    out = pl.pallas_call(
        functools.partial(_probe_kernel, et=et, ns=n_slots, w=w,
                          max_probes=max_probes),
        grid=(ne,),
        in_specs=[
            pl.BlockSpec((r, rw), lambda i: (0, 0)),
            pl.BlockSpec((et, META_COLS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((et, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ne * et, w), jnp.int32),
        interpret=interpret,
    )(flat_records, meta)
    return out[:e]
