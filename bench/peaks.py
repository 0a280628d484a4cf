"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A device kind that is not in the table is an error: a share
of a peak is never computed against a guessed one.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9, "ici_bw": 200e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
