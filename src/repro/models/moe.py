"""Top-k token-choice Mixture-of-Experts with capacity-based dispatch.

Routing: softmax router (f32) -> top-k -> renormalize -> capacity-gated
scatter dispatch into per-expert buffers [E, C, d] -> batched SwiGLU experts
-> weighted combine. Tokens over capacity are dropped (their MoE output is 0,
residual stream carries them through) — GShard/Switch semantics.

The [E, C, d] buffers shard E over the "model" mesh axis (expert parallelism);
the scatter/gather are the dispatch/combine "all-to-all"s. The aux losses are
the standard load-balancing loss + router z-loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig


def _constrain_experts_to_model_axis(x: jax.Array) -> jax.Array:
    """Pin dim 0 (experts) to the "model" mesh axis when a mesh is ambient;
    no-op on single-device/smoke runs."""
    try:
        from jax.sharding import PartitionSpec as _P
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in (mesh.axis_names or ()):
            return x
        U = _P.UNCONSTRAINED
        return jax.lax.with_sharding_constraint(
            x, _P("model", *([U] * (x.ndim - 1))))
    except Exception:
        return x


def init_moe(key: jax.Array, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(pd),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(pd),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(pd),
    }


def apply_moe(p: dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, dict]:
    """x: [..., d] (usually [B, S, d]). Returns (y, aux).

    With ``moe_group_by_batch`` the dispatch is vmapped over the batch dim:
    capacity is per-row, the [B, E, C, d] buffers shard their leading dim
    with the batch — routing never crosses the (pod, data) axes."""
    if cfg.moe_group_by_batch and x.ndim == 3:
        # GSPMD cannot batch-partition top_k / scatter-add: it all-gathers
        # the router probs and dispatch buffers across the batch axes (the
        # inter-DC catastrophe measured in EXPERIMENTS.md §Perf). shard_map
        # over the batch axes makes routing shard-local BY CONSTRUCTION;
        # expert compute stays auto. Requires expert weights replicated over
        # the batch axes (ShardingRules does this when moe_group_by_batch).
        mesh = jax.sharding.get_abstract_mesh()
        axes = tuple(a for a in ("pod", "data")
                     if not mesh.empty and a in mesh.axis_names)
        if axes:
            from jax.sharding import PartitionSpec as P2

            def local_fn(xt, pp):
                b, s, d = xt.shape
                y, aux = _moe_tokens(pp, xt.reshape(b * s, d), cfg)
                aux = {k: jax.lax.pmean(v, axes) for k, v in aux.items()}
                return y.reshape(b, s, d), aux

            # FULL-manual shard_map (all mesh axes): expert weights are
            # replicated (EP->DP for grouped mode), so the entire MoE layer
            # is collective-free and shard-local by construction.
            fn = jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(P2(axes, None, None),
                          jax.tree.map(lambda _: P2(), p)),
                out_specs=(P2(axes, None, None),
                           dict(moe_lb_loss=P2(), moe_z_loss=P2(),
                                moe_drop_frac=P2())),
                check_vma=False)
            return fn(x, p)
        # single-device / no-mesh fallback: per-row routing via vmap
        y, aux = jax.vmap(lambda row: _moe_tokens(p, row, cfg,
                                                  grouped=True))(x)
        return y, {k: v.mean() for k, v in aux.items()}
    orig_shape = x.shape
    y, aux = _moe_tokens(p, x.reshape(-1, orig_shape[-1]), cfg)
    return y.reshape(orig_shape), aux


def _moe_tokens(p: dict, xt: jax.Array, cfg: ModelConfig,
                grouped: bool = False) -> Tuple[jax.Array, dict]:
    """xt: [T, d] flat tokens. ``grouped``: running under vmap-over-batch —
    pin the expert dim of the dispatch buffers to the "model" axis so the
    exchange is an intra-pod model-axis all-to-all (proper expert
    parallelism), never a (pod, data) token gather."""
    d = xt.shape[-1]
    t = xt.shape[0]
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = max(int(cfg.moe_capacity_factor * t * k / e), k)

    # --- routing (f32) ---
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]
    gate_vals, idx = jax.lax.top_k(probs, k)                 # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- aux losses ---
    # load balance: E * sum_e f_e * p_e  (f: fraction dispatched, p: mean prob)
    onehot_top1_frac = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (t * k))
    mean_prob = probs.mean(axis=0)
    lb_loss = e * jnp.sum(onehot_top1_frac * mean_prob)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # --- capacity positions: pos of slot (t, j) inside expert idx[t, j] ---
    flat_e = idx.reshape(-1)                                 # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # [T*k, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot           # count before me
    flat_pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < cap
    safe_pos = jnp.where(keep, flat_pos, cap)                # cap -> dropped

    # --- dispatch: scatter tokens into [E, C+1, d]; last slot is the drop bin
    upd = jnp.repeat(xt, k, axis=0)                          # [T*k, d]
    buf = jnp.zeros((e, cap + 1, d), xt.dtype)
    buf = buf.at[flat_e, safe_pos].add(upd)
    buf = buf[:, :cap]                                       # [E, C, d]
    if grouped:
        buf = _constrain_experts_to_model_axis(buf)

    # --- experts (batched SwiGLU) ---
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])         # [E, C, d]
    if grouped:
        out = _constrain_experts_to_model_axis(out)

    # --- combine: gather back, weight by gates, zero dropped ---
    out_pad = jnp.concatenate([out, jnp.zeros((e, 1, d), out.dtype)], axis=1)
    gathered = out_pad[flat_e, safe_pos]                     # [T*k, d]
    w = (gate_vals.reshape(-1) * keep.astype(jnp.float32)).astype(gathered.dtype)
    y = (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)

    aux = {
        "moe_lb_loss": lb_loss,
        "moe_z_loss": z_loss,
        "moe_drop_frac": 1.0 - keep.astype(jnp.float32).mean(),
    }
    return y, aux
