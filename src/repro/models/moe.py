"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Design notes (TPU / GSPMD):
  * Dispatch is gather/scatter based, NOT the GShard dense one-hot einsum —
    the dense dispatch einsum costs ``O(k*cf*S^2*D)`` MACs per group which can
    exceed the expert FLOPs by >100x for high-k models (deepseek k=6).
  * Tokens are grouped; all routing bookkeeping (sort, cumsum) is local to a
    group, and groups are sharded over the ``data`` axis, so routing itself
    never communicates.  The dispatched buffer is sharding-constrained to
    experts-over-``model``; GSPMD materialises the EP all-to-all there.
  * Capacity-bounded with token dropping (standard); capacity factor config.

Supports deepseek-style shared experts and arctic-style parallel dense
residual FFN.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig, MoEConfig
from repro.models.layers import act_fn, init_glu_mlp, apply_glu_mlp, truncated_normal


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    ks = jax.random.split(key, 6)
    p = {
        "router": truncated_normal(ks[0], (d, m.num_experts), d ** -0.5, jnp.float32),
        "wi_gate": truncated_normal(ks[1], (m.num_experts, d, f), d ** -0.5, dtype),
        "wi_up": truncated_normal(ks[2], (m.num_experts, d, f), d ** -0.5, dtype),
        "wo": truncated_normal(ks[3], (m.num_experts, f, d), f ** -0.5, dtype),
    }
    if m.num_shared_experts:
        p["shared"] = init_glu_mlp(ks[4], d, f * m.num_shared_experts, dtype)
    if m.dense_residual_d_ff:
        p["dense_residual"] = init_glu_mlp(ks[5], d, m.dense_residual_d_ff, dtype)
    return p


def _capacity(tokens_per_group: int, m: MoEConfig) -> int:
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, (c + 7) // 8 * 8)  # MXU-friendly multiple of 8


def route_topk(router_w, x, m: MoEConfig):
    """x: (G, S, D) -> gates (G,S,k) f32, idx (G,S,k) i32, aux losses."""
    logits = x.astype(jnp.float32) @ router_w  # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balance loss + router z-loss
    me = jnp.mean(probs, axis=(0, 1))                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=2),
        axis=(0, 1),
    )                                                                  # (E,)
    lb_loss = m.num_experts * jnp.sum(me * ce) / m.top_k
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb_loss": lb_loss * m.load_balance_loss,
           "moe_z_loss": z_loss * m.router_z_loss}
    return gates, idx, aux


def _dispatch_indices(idx: jnp.ndarray, num_experts: int, capacity: int):
    """idx: (G, S, k) expert assignment -> per-slot destination in an
    (E*C)-slot buffer, plus validity mask and source-token index.

    All ops are local to a group (axis -1 sorts)."""
    g, s, k = idx.shape
    flat_e = idx.reshape(g, s * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)          # (G, S*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    # counts per expert via batched scatter-add
    counts = jnp.zeros((g, num_experts), jnp.int32)
    counts = jax.vmap(lambda c, e: c.at[e].add(1))(counts, flat_e)
    offsets = jnp.cumsum(counts, axis=-1) - counts             # exclusive
    pos = jnp.arange(s * k)[None, :] - jnp.take_along_axis(offsets, sorted_e, axis=-1)
    valid = pos < capacity
    dest = jnp.where(valid, sorted_e * capacity + pos, num_experts * capacity)
    token = order // k                                          # source token per slot
    kslot = order % k                                           # which top-k slot
    return dest, valid, token, kslot, order


def apply_moe(p, cfg: ModelConfig, x, capacity: Optional[int] = None):
    """x: (B, S, D) -> (B, S, D), aux_losses dict.

    Groups = batch entries (already data-sharded); routing is group-local.
    """
    m = cfg.moe
    b, s, d = x.shape
    if s == 1 and b > 1:
        # decode: fold the batch into one routing group (per-token groups
        # would waste an entire capacity buffer per token). NOTE: Perf
        # cell 3 iteration 2 tried 16 data-sharded groups instead — it made
        # the collective term ~9x WORSE (per-group dispatch bookkeeping
        # dominates at 8 tokens/group); the single group stays.
        out, aux = apply_moe(p, cfg, x.reshape(1, b, d), capacity)
        return out.reshape(b, s, d), aux
    cap = capacity if capacity is not None else _capacity(s, m)
    e = m.num_experts

    gates, idx, aux = route_topk(p["router"], x, m)
    dest, valid, token, kslot, order = _dispatch_indices(idx, e, cap)

    # ---- dispatch: gather tokens into (G, E*C, D), experts-major ----------
    slot_vals = jnp.take_along_axis(x, token[..., None], axis=1)   # (G, S*k, D)
    buf = jnp.zeros((b, e * cap + 1, d), x.dtype)
    buf = jax.vmap(lambda bb, dd, vv: bb.at[dd].set(vv, mode="drop"))(buf, dest, slot_vals)
    expert_in = buf[:, : e * cap].reshape(b, e, cap, d)
    # EP: experts over the model axis; groups stay on data
    expert_in = _maybe_shard(expert_in, ("data", "model", None, None))

    # ---- expert computation (batched over E) -------------------------------
    wi_g = p["wi_gate"].astype(x.dtype)
    wi_u = p["wi_up"].astype(x.dtype)
    wo = p["wo"].astype(x.dtype)
    h = act_fn(cfg.act)(jnp.einsum("gecd,edf->gecf", expert_in, wi_g))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, wi_u)
    expert_out = jnp.einsum("gecf,efd->gecd", h, wo)
    expert_out = _maybe_shard(expert_out, ("data", "model", None, None))

    # ---- combine: gather back and weight by gates ---------------------------
    flat_out = expert_out.reshape(b, e * cap, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    slot_out = jnp.take_along_axis(flat_out, jnp.minimum(dest, e * cap)[..., None], axis=1)
    slot_out = jnp.where(valid[..., None], slot_out, 0)
    # scatter slots back to (token, kslot) order
    inv = jnp.argsort(order, axis=-1)
    slot_out = jnp.take_along_axis(slot_out, inv[..., None], axis=1)   # (G, S*k, D)
    slot_out = slot_out.reshape(b, s, m.top_k, d)
    out = jnp.einsum("gskd,gsk->gsd", slot_out, gates.astype(x.dtype))

    # ---- shared experts / dense residual ------------------------------------
    if "shared" in p:
        out = out + apply_glu_mlp(p["shared"], x, cfg.act)
    if "dense_residual" in p:
        out = out + apply_glu_mlp(p["dense_residual"], x, cfg.act)
    return out, aux


def _maybe_shard(x, spec):
    """with_sharding_constraint if a mesh with the named axes is active.

    ``spec`` entries may be axis names or tuples of axis names; entries for
    axes absent from the mesh or that do not divide the dim are dropped."""
    from jax.sharding import PartitionSpec as P

    from repro.common import jax_compat as jc
    mesh = jc.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    ok = []
    for dim, ax in zip(x.shape, spec):
        axes = tuple(a for a in ((ax,) if isinstance(ax, str) or ax is None else ax)
                     if a in names)
        if not axes:
            ok.append(None)
            continue
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if dim % total == 0:
            ok.append(axes if len(axes) > 1 else axes[0])
        else:
            ok.append(None)
    if all(a is None for a in ok):
        return x
    return jax.lax.with_sharding_constraint(x, P(*ok))
