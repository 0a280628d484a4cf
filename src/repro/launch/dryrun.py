import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices host the production meshes; every cell must .lower().compile(), and
we record memory_analysis / cost_analysis / scan-aware HLO costs for the
roofline (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out runs/dryrun [--force]
"""

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro.analysis import hlo as hlo_analysis                      # noqa: E402
from repro.configs import ARCH_IDS, get_config                      # noqa: E402
from repro.configs.base import SHAPES, TrainConfig, shape_by_name   # noqa: E402
from repro.distributed.sharding import (batch_shardings,            # noqa: E402
                                        cache_specs, param_shardings)
from repro.launch import specs as S                                 # noqa: E402
from repro.launch.mesh import make_production_mesh                  # noqa: E402
from repro.models import build                                      # noqa: E402
from repro.train.optimizer import init_opt_state                    # noqa: E402
from repro.train.train_loop import jit_train_step                   # noqa: E402
from jax.sharding import NamedSharding                              # noqa: E402


def lower_cell(arch: str, shape_name: str, mesh, *, tc: TrainConfig,
               cfg_overrides: dict | None = None):
    """Build + lower + compile one cell. Returns (lowered, compiled, meta).

    ``cfg_overrides`` supports the §Perf hillclimb: the same cell re-lowered
    with e.g. {"fused_qkv": True} or {"param_dtype": "bfloat16"}.
    """
    import dataclasses
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = shape_by_name(shape_name)
    ok, why = S.runnable(cfg, shape)
    if not ok:
        return None, None, {"status": "skipped", "reason": why}

    bundle = build(cfg)
    p_shape = S.params_shape(bundle)
    tp = mesh.shape["model"]
    t0 = time.time()

    if shape.kind == "train":
        batch = S.input_specs(cfg, shape)
        step = jit_train_step(bundle, tc, mesh, p_shape, batch)
        opt_shape = jax.eval_shape(init_opt_state, p_shape)
        lowered = step.lower(p_shape, opt_shape, batch)
    elif shape.kind == "prefill":
        batch = S.input_specs(cfg, shape)
        p_shard = param_shardings(p_shape, mesh)
        b_shard = batch_shardings(batch, mesh)

        def prefill_fn(params, b):
            return bundle.prefill(params, b, mesh=mesh, tp=tp,
                                  max_len=shape.seq_len)

        lowered = jax.jit(prefill_fn,
                          in_shardings=(p_shard, b_shard)).lower(p_shape, batch)
    else:  # decode
        from repro.distributed.sharding import batch_axes as _baxes
        import numpy as _np
        cache = S.cache_shape(bundle, cfg, shape, tp, p_shape=p_shape)
        token = S.token_specs(cfg, shape)
        # batch-starved decode (e.g. long_500k, B=1): the data axes would
        # replicate the work — shard tensor dims over (data x model) instead
        # (2D serve sharding, EXPERIMENTS.md §Perf D). Gated on the arch's
        # dims dividing the full axis product: partial divisibility makes the
        # partitioner reshard mid-layer and costs more than it saves
        # (measured: hymba/danube regress 3-5x).
        n_batch = int(_np.prod([mesh.shape[a] for a in _baxes(mesh)]))
        n_total = n_batch * mesh.shape["model"]
        fits_2d = (cfg.family == "ssm"
                   and cfg.d_inner % n_total == 0
                   and cfg.vocab_size % n_total == 0)
        if shape.global_batch % n_batch != 0 and fits_2d:
            tensor_axes = tuple(_baxes(mesh)) + ("model",)
        else:
            tensor_axes = "model"
        p_shard = param_shardings(p_shape, mesh, tensor_axes=tensor_axes)
        c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               cache_specs(cache, mesh,
                                           tensor_axes=tensor_axes))
        t_shard = batch_shardings({"token": token}, mesh)["token"]

        def serve_step(params, c, tok):
            return bundle.decode_step(params, c, tok, mesh=mesh)

        lowered = jax.jit(serve_step,
                          in_shardings=(p_shard, c_shard, t_shard),
                          donate_argnums=(1,)).lower(p_shape, cache, token)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    meta = {"status": "ok", "lower_s": t_lower, "compile_s": t_compile}
    return lowered, compiled, meta


def analyze_cell(arch: str, shape_name: str, mesh, mesh_name: str,
                 tc: TrainConfig) -> dict:
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mesh_shape": dict(mesh.shape), "n_chips": mesh.size,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    try:
        lowered, compiled, meta = lower_cell(arch, shape_name, mesh, tc=tc)
        rec.update(meta)
        if meta["status"] == "skipped":
            return rec
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        }
        ca = compiled.cost_analysis()
        rec["xla_cost"] = {k: ca[k] for k in ("flops", "bytes accessed")
                           if k in ca}
        txt = compiled.as_text()
        rec["hlo_chars"] = len(txt)
        cost = hlo_analysis.analyze(txt)
        rec["hlo_cost"] = {
            "flops": cost.flops, "bytes": cost.bytes,
            "bytes_naive": cost.bytes_naive,
            "collective_bytes": cost.collective_bytes,
            "collective_breakdown": cost.collective_breakdown,
            "n_collectives": cost.n_collectives,
        }
    except Exception as e:  # a failing cell is a bug; record it loudly
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [
        a.replace("-", "_").replace(".", "_") for a in args.arch.split(",")]
    shapes = [s.name for s in SHAPES] if args.shape == "all" \
        else args.shape.split(",")
    mesh_names = {"single": ["single_pod"], "multi": ["multi_pod"],
                  "both": ["single_pod", "multi_pod"]}[args.mesh]
    tc = TrainConfig()

    os.makedirs(args.out, exist_ok=True)
    meshes = {}
    for mesh_name in mesh_names:
        meshes[mesh_name] = make_production_mesh(
            multi_pod=(mesh_name == "multi_pod"))

    for mesh_name in mesh_names:
        mesh = meshes[mesh_name]
        for arch in archs:
            for shape_name in shapes:
                path = os.path.join(args.out,
                                    f"{mesh_name}__{arch}__{shape_name}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip cached] {path}")
                    continue
                t0 = time.time()
                rec = analyze_cell(arch, shape_name, mesh, mesh_name, tc)
                rec["wall_s"] = time.time() - t0
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compile {rec['compile_s']:.1f}s "
                             f"flops/dev {rec['hlo_cost']['flops']:.3e} "
                             f"coll {rec['hlo_cost']['collective_bytes']:.3e}B")
                elif status == "error":
                    extra = rec["error"][:160]
                print(f"[{status}] {mesh_name} {arch} {shape_name} "
                      f"({rec['wall_s']:.1f}s) {extra}", flush=True)


if __name__ == "__main__":
    main()
