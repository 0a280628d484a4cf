"""Three-term roofline from dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch, shape, mesh) cell — all terms are per-chip seconds per step:

    compute    = HLO_FLOPs_per_chip / PEAK_FLOPS
    memory     = HLO_bytes_per_chip / HBM_BW
    collective = collective_wire_bytes_per_chip / ICI_BW

HLO numbers come from the scan-aware analyzer (analysis/hlo.py) — XLA's own
cost_analysis counts while bodies once and is reported alongside for reference.
MODEL_FLOPS follows the assignment: 6*N*D for training, 2*N*D for inference
forward passes, with N = active parameters (MoE: top-k experts only).

Hardware model (TPU v5e-like, from the assignment):
    197 TFLOP/s bf16 per chip; 819 GB/s HBM; 50 GB/s/link ICI.
We charge collectives against a single 50 GB/s link per chip (conservative: a
2D-torus ring uses both directions of one axis; using 2 links would halve the
collective term).
"""

from __future__ import annotations

import glob
import json
import os

# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s inter-chip interconnect).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9, "ici_bw": 200e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    """The peak table row for a device; an unknown kind is an error, never
    a silent default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


# the dry-run models a v5e pod (the assignment's hardware model)
_V5E = PEAKS["TPU v5 lite"]
PEAK_FLOPS = _V5E["bf16_flops"]     # bf16 / chip
HBM_BW = _V5E["hbm_bw"]             # bytes/s / chip
ICI_BW = 50e9                       # bytes/s / link (1 link charged)
HBM_PER_CHIP = _V5E["hbm_bytes"]


def model_flops(rec: dict) -> float:
    """Assignment definition, on the whole (global) step."""
    n = rec["active_params"]
    if rec["kind"] == "train":
        tokens = rec["global_batch"] * rec["seq_len"]
        return 6.0 * n * tokens
    if rec["kind"] == "prefill":
        tokens = rec["global_batch"] * rec["seq_len"]
        return 2.0 * n * tokens
    return 2.0 * n * rec["global_batch"]     # decode: one token per sequence


def roofline(rec: dict) -> dict:
    """Derive the three terms + bottleneck for one dry-run record."""
    hc = rec["hlo_cost"]
    chips = rec["n_chips"]
    compute_s = hc["flops"] / PEAK_FLOPS
    memory_s = hc["bytes"] / HBM_BW
    collective_s = hc["collective_bytes"] / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    useful = mf / (hc["flops"] * chips) if hc["flops"] else 0.0
    bound = max(terms.values())
    # fraction of the achievable roofline this step reaches if it ran exactly
    # at the dominant term (ideal overlap of the other two):
    step_ideal = mf / chips / PEAK_FLOPS   # time if compute were 100% useful
    frac = step_ideal / bound if bound > 0 else 0.0
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
        "model_flops": mf, "useful_flops_ratio": useful,
        "roofline_fraction": frac,
        "hbm_args_frac": rec["memory"]["argument_bytes"] / HBM_PER_CHIP,
    }


def load_records(dirpath: str, mesh: str | None = None) -> list[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if mesh and rec.get("mesh") != mesh:
            continue
        recs.append(rec)
    return recs


def _fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def report_markdown(dirpath: str, mesh: str = "single_pod") -> str:
    """Roofline table (single-pod by assignment) + dry-run status table."""
    recs = load_records(dirpath)
    lines = []

    lines.append(f"### Dry-run status ({len(recs)} cells)\n")
    lines.append("| mesh | arch | shape | status | compile | bytes/dev (args) | note |")
    lines.append("|---|---|---|---|---|---|---|")
    for r in recs:
        if r["status"] == "ok":
            note = (f"flops/dev {r['hlo_cost']['flops']:.2e}, "
                    f"coll {r['hlo_cost']['collective_bytes']:.2e} B")
            mem = f"{r['memory']['argument_bytes'] / 1e9:.2f} GB"
            comp = f"{r['compile_s']:.0f}s"
        elif r["status"] == "skipped":
            note, mem, comp = r["reason"], "-", "-"
        else:
            note, mem, comp = r.get("error", "?")[:80], "-", "-"
        lines.append(f"| {r['mesh']} | {r['arch']} | {r['shape']} | "
                     f"{r['status']} | {comp} | {mem} | {note} |")

    lines.append(f"\n### Roofline ({mesh}, per chip per step)\n")
    lines.append("| arch | shape | compute | memory | collective | dominant | "
                 "MODEL_FLOPS | useful ratio | roofline frac |")
    lines.append("|---|---|---|---|---|---|---|---|---|")
    for r in recs:
        if r.get("mesh") != mesh or r["status"] != "ok":
            continue
        t = roofline(r)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {_fmt_s(t['compute_s'])} | "
            f"{_fmt_s(t['memory_s'])} | {_fmt_s(t['collective_s'])} | "
            f"**{t['dominant']}** | {t['model_flops']:.2e} | "
            f"{t['useful_flops_ratio']:.2f} | {t['roofline_fraction']:.2f} |")
    return "\n".join(lines)


VPU_OPS = 4e12   # ~VPU element-op throughput per chip (order-of-magnitude;
                 # the MXU peak does not apply to select/min workloads)


def cminhash_kernel_roofline(b: int, d: int, k: int, *, block_b: int = 8,
                             block_d: int = 256, packed: bool = False) -> dict:
    """Analytic roofline for the dense circulant-min kernel (§Perf).

    Per grid cell (Bt, Kt=Dt, Dt): band read (2*Bt*Dt bytes int8, /8 packed),
    pi read (4*Dt), out write (4*Bt*Kt, once per (i,j)); compute = 2 VPU ops
    (select+min) per (b, k, d) element.
    """
    bt, dt = block_b, block_d
    kt = dt
    nb, nk, nd = -(-b // bt), -(-k // kt), -(-d // dt)
    band = 2 * bt * dt * (1 / 8 if packed else 1)
    bytes_ = nb * nk * nd * (band + 4 * dt) + nb * nk * (4 * bt * kt)
    ops = 2.0 * b * k * d
    compute_s = ops / VPU_OPS
    memory_s = bytes_ / HBM_BW
    return {
        "ops": ops, "bytes": bytes_,
        "compute_s": compute_s, "memory_s": memory_s,
        "dominant": "compute" if compute_s >= memory_s else "memory",
        "arith_intensity": ops / bytes_,
    }


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="runs/dryrun")
    ap.add_argument("--mesh", default="single_pod")
    args = ap.parse_args()
    print(report_markdown(args.dir, args.mesh))


if __name__ == "__main__":
    main()
