"""Top-k MoE with shard_map expert parallelism.

Experts shard over the ``model`` mesh axis. Routing (a small matmul + top_k) runs
in plain pjit-land; the expert FFN runs inside ``jax.shard_map``: every model
shard applies its local experts to the local data-shard's tokens at a fixed
capacity, and shard outputs are combined with a single ``psum`` over ``model`` —
the same wire cost as a Megatron MLP all-reduce, with no data-dependent
collectives for XLA to guess at (DESIGN.md §5). Over-capacity tokens are dropped
(GShard semantics); the router aux loss encourages balance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .layers import normal_init

Array = jax.Array


def init_moe(key: Array, cfg, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    down_scale = f ** -0.5 / np.sqrt(2 * cfg.n_layers)
    return {
        "router": normal_init(ks[0], (d, e), scale, jnp.float32),
        "e_gate": normal_init(ks[1], (e, d, f), scale, dtype),
        "e_up": normal_init(ks[2], (e, d, f), scale, dtype),
        "e_down": normal_init(ks[3], (e, f, d), down_scale, dtype),
    }


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(np.ceil(n_tokens * top_k / n_experts * factor))
    return max(8, -(-c // 8) * 8)


def _expert_ffn(xf: Array, idx: Array, gates: Array, wg: Array, wu: Array,
                wd: Array, *, e_offset, n_experts_total: int,
                capacity: int) -> Array:
    """Apply local experts to local tokens at fixed capacity.

    xf: (T, D); idx: (T, k) global expert ids; gates: (T, k); wg/wu: (El, D, F);
    wd: (El, F, D); e_offset: first global id owned locally. Returns (T, D).
    """
    t, k = idx.shape
    el = wg.shape[0]
    d = xf.shape[-1]
    dtype = xf.dtype

    lid = idx.reshape(-1) - e_offset                      # (T*k,) local ids
    valid = (lid >= 0) & (lid < el)
    lid_safe = jnp.where(valid, lid, 0)

    onehot = jax.nn.one_hot(jnp.where(valid, lid, el), el, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                  # position within expert
    pos = jnp.take_along_axis(pos, lid_safe[:, None], axis=1)[:, 0]
    keep = valid & (pos < capacity)

    slot = jnp.where(keep, lid_safe * capacity + pos, el * capacity)  # drop idx
    token_of = jnp.repeat(jnp.arange(t), k)

    buf = jnp.zeros((el * capacity, d), dtype)
    buf = buf.at[slot].add(xf[token_of], mode="drop")
    buf = buf.reshape(el, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(dtype))
    out = out.reshape(el * capacity, d)

    contrib = jnp.where(keep, gates.reshape(-1), 0.0).astype(dtype)
    y = jnp.zeros((t, d), dtype)
    y = y.at[token_of].add(out[jnp.clip(slot, 0, el * capacity - 1)]
                           * contrib[:, None])
    return y


def _route(xf: Array, router_w: Array, e: int, k: int):
    """Router: top-k gates + load-balance aux. Pure per-token math — safe to
    run per shard (no cross-token state)."""
    logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                            # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    assign = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.mean(jnp.mean(assign, axis=0) * jnp.mean(probs, axis=0))
    return gates, idx, aux


def moe_block(p: dict, x: Array, cfg, mesh=None) -> tuple[Array, Array]:
    """x: (B, S, D) -> (y: (B, S, D), aux_loss scalar).

    Expert-parallel path: routing runs INSIDE the shard_map (top_k on the
    local token shard — the partitioner otherwise all-gathers the full (T, E)
    probs), and tokens cross the shard boundary sharded over ``model`` on the
    feature dim with an explicit in-body all_gather. Its transpose is a
    reduce-scatter at (T_loc, D/tp) — without this, the backward all-reduces
    the pre-scatter (T_loc*k, D) cotangent, ~15x more wire (measured in
    EXPERIMENTS.md §Perf).
    """
    b, s, d = x.shape
    dtype = x.dtype
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(b * s, d)

    tp = mesh.shape["model"] if mesh is not None and "model" in \
        getattr(mesh, "axis_names", ()) else 1
    use_ep = tp > 1 and e % tp == 0 and d % tp == 0
    if use_ep:
        el = e // tp
        batch_axes = tuple(a for a in mesh.axis_names if a != "model")
        n_data = int(np.prod([mesh.shape[a] for a in batch_axes]))
        n_mesh = int(np.prod(list(mesh.shape.values())))
        cap = _capacity(b * s // n_data, e, k, cfg.capacity_factor)
        from jax.sharding import PartitionSpec as P

        def body(x_shard, router_w, wg, wu, wd):
            xl = jax.lax.all_gather(x_shard, "model", axis=1, tiled=True)
            gl, il, aux = _route(xl, router_w, e, k)
            off = jax.lax.axis_index("model") * el
            y = _expert_ffn(xl, il, gl.astype(dtype), wg, wu, wd,
                            e_offset=off, n_experts_total=e, capacity=cap)
            y = jax.lax.psum(y, "model")
            aux = jax.lax.psum(aux, tuple(mesh.axis_names)) / n_mesh
            return y, aux

        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(batch_axes, "model"), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=(P(batch_axes, None), P()),
        )(xf, p["router"],
          p["e_gate"].astype(dtype), p["e_up"].astype(dtype),
          p["e_down"].astype(dtype))
    else:
        gates, idx, aux = _route(xf, p["router"], e, k)
        cap = _capacity(b * s, e, k, cfg.capacity_factor)
        y = _expert_ffn(xf, idx, gates.astype(dtype), p["e_gate"], p["e_up"],
                        p["e_down"], e_offset=0, n_experts_total=e,
                        capacity=cap)
    return y.reshape(b, s, d), aux
