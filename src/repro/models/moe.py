"""Mixture-of-Experts FFN: a capacity-bounded layer over all experts, and
a layer that holds a share of them on a fixed device budget.

``apply_moe`` picks by the configuration: ``MoEConfig.experts_held``
> 0 selects the held-expert layer (``apply_held_moe``, DeepSeek-V2-Lite),
otherwise the per-expert capacity layer below (arctic-480b,
deepseek-v2-236b: the 16x16 dry-run mesh shards its experts over ``model``).

Per-expert capacity layer, design notes (TPU / GSPMD):
  * Dispatch is gather/scatter based, NOT the GShard dense one-hot einsum —
    the dense dispatch einsum costs ``O(k*cf*S^2*D)`` MACs per group which can
    exceed the expert FLOPs by >100x for high-k models (deepseek k=6).
  * Tokens are grouped; all routing bookkeeping (sort, cumsum) is local to a
    group, and groups are sharded over the ``data`` axis, so routing itself
    never communicates.  The dispatched buffer is sharding-constrained to
    experts-over-``model``; GSPMD materialises the EP all-to-all there.
  * Capacity-bounded with token dropping (standard); capacity factor config.

Held-expert layer: routes over all experts, keeps a fixed budget of the
(token, expert) rows routed to its own experts and runs one grouped matrix
product over them (``apply_held_moe``).

Both support deepseek-style shared experts; the capacity layer also
arctic-style parallel dense residual FFN.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig, MoEConfig
from repro.models.layers import act_fn, init_glu_mlp, apply_glu_mlp, truncated_normal


#: the held-expert layer's row budget is a whole number of these
BUDGET_TILE = 128


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    """The router scores all ``num_experts``; the expert weights are those
    the layer holds (all of them unless ``experts_held`` is set)."""
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    e = m.experts_held or m.num_experts
    ks = jax.random.split(key, 6)
    p = {
        "router": truncated_normal(ks[0], (d, m.num_experts), d ** -0.5, jnp.float32),
        "wi_gate": truncated_normal(ks[1], (e, d, f), d ** -0.5, dtype),
        "wi_up": truncated_normal(ks[2], (e, d, f), d ** -0.5, dtype),
        "wo": truncated_normal(ks[3], (e, f, d), f ** -0.5, dtype),
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
    """x: (G, S, D) -> gates (G,S,k) f32, idx (G,S,k) i32, aux losses.

    Softmax over all experts' float32 logits, then the top k; the gates are
    renormalised only with ``norm_topk_prob``.  The balance loss is
    Switch-style over the whole batch, or with ``seq_aux`` DeepSeek-V2's
    per-sequence form averaged over sequences; the router z-loss is added
    where its factor is set."""
    logits = x.astype(jnp.float32) @ router_w  # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    counts = jnp.sum(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=2)
    axes = (1,) if m.seq_aux else (0, 1)
    me = jnp.mean(probs, axis=axes)                                    # ([G,] E)
    ce = jnp.mean(counts, axis=axes)                                   # ([G,] E)
    lb_loss = m.num_experts * jnp.mean(jnp.sum(me * ce, axis=-1)) / m.top_k
    aux = {"moe_lb_loss": lb_loss * m.load_balance_loss}
    if m.router_z_loss:
        z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux["moe_z_loss"] = z_loss * m.router_z_loss
    return gates, idx, aux


def aux_zeros(m: MoEConfig) -> Dict[str, jnp.ndarray]:
    """The aux outputs of one MoE layer, zero: losses, and for the
    held-expert layer its kept and dropped row counts."""
    out = {"moe_lb_loss": jnp.zeros((), jnp.float32)}
    if m.router_z_loss:
        out["moe_z_loss"] = jnp.zeros((), jnp.float32)
    if m.experts_held:
        out["moe_rows_kept"] = jnp.zeros((), jnp.int32)
        out["moe_rows_dropped"] = jnp.zeros((), jnp.int32)
    return out


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


def budget_rows(tokens: int, m: MoEConfig) -> int:
    """The held-expert layer's fixed row budget for ``tokens`` tokens:
    ceil(capacity_factor * tokens * top_k * experts_held / num_experts),
    rounded up to a whole ``BUDGET_TILE``."""
    rows = math.ceil(m.capacity_factor * tokens * m.top_k * m.experts_held
                     / m.num_experts)
    return -(-rows // BUDGET_TILE) * BUDGET_TILE


def apply_held_moe(p, cfg: ModelConfig, x, rows=None):
    """The layer's share of a routed MoE FFN on a fixed row budget.

    x: (B, S, D); ``rows`` (B,) is each sequence's index in the global
    batch (its position in ``x`` by default).  Routing is over all
    ``num_experts``; of the (token, expert) rows routed to the held experts
    [expert_start, expert_start + held), the ``budget_rows`` with the
    highest priority are kept: rows of protected sequences first, then the
    higher gate affinity (one sort on affinity + 1 for protected rows).
    The rest are dropped, and empty budget slots carry zeros, so the expert
    work has one shape whatever the routing.  The kept rows, sorted by
    expert, go through one grouped product per weight, and come back
    weighted by their gates.  Returns (out, aux) with the aux losses and
    the kept and dropped row counts."""
    m = cfg.moe
    b, s, d = x.shape
    k, held = m.top_k, m.experts_held
    t = b * s
    budget = budget_rows(t, m)
    with jax.named_scope("moe.route"):
        gates, idx, aux = route_topk(p["router"], x, m)
    with jax.named_scope("moe.dispatch"):
        local = idx.reshape(t * k) - m.expert_start
        mine = (local >= 0) & (local < held)
        if rows is None:
            rows = jnp.arange(b)
        protected = (rows % m.protected_every == 0 if m.protected_every
                     else jnp.zeros((b,), bool))
        bonus = jnp.broadcast_to(protected[:, None, None], (b, s, k)).reshape(t * k)
        flat_gates = gates.reshape(t * k)
        priority = jnp.where(mine, flat_gates + bonus.astype(jnp.float32), -1.0)
        if budget < t * k:
            kept_priority, slot = jax.lax.top_k(priority, budget)
        else:
            slot = jnp.arange(budget) % (t * k)
            kept_priority = jnp.where(jnp.arange(budget) < t * k, priority[slot], -1.0)
        valid = kept_priority >= 0
        expert = jnp.where(valid, local[slot], held)
        expert, slot, valid = jax.lax.sort((expert, slot, valid), num_keys=1,
                                           is_stable=True)
        # empty slots (expert == held) join the last group: zero rows
        sizes = jnp.bincount(expert, length=held + 1)
        group_sizes = sizes[:held].at[held - 1].add(sizes[held])
        token = slot // k
        xs = jnp.where(valid[:, None], x.reshape(t, d)[token], 0).astype(x.dtype)
        row_gate = jnp.where(valid, flat_gates[slot], 0.0)
    with jax.named_scope("moe.experts"):
        wi_g = p["wi_gate"].astype(x.dtype)
        wi_u = p["wi_up"].astype(x.dtype)
        wo = p["wo"].astype(x.dtype)
        h = act_fn(cfg.act)(jax.lax.ragged_dot(xs, wi_g, group_sizes))
        h = h * jax.lax.ragged_dot(xs, wi_u, group_sizes)
        y = jax.lax.ragged_dot(h, wo, group_sizes)
    with jax.named_scope("moe.combine"):
        out = jnp.zeros((t, d), jnp.float32).at[token].add(
            y.astype(jnp.float32) * row_gate[:, None])
        out = out.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        out = out + apply_glu_mlp(p["shared"], x, cfg.act)
    kept = jnp.sum(valid, dtype=jnp.int32)
    aux["moe_rows_kept"] = kept
    aux["moe_rows_dropped"] = jnp.sum(mine, dtype=jnp.int32) - kept
    return out, aux


def apply_moe(p, cfg: ModelConfig, x, capacity: Optional[int] = None, rows=None):
    """x: (B, S, D) -> (B, S, D), aux_losses dict.

    Groups = batch entries (already data-sharded); routing is group-local.
    A configuration with a device budget runs ``apply_held_moe`` instead.
    """
    m = cfg.moe
    if m.experts_held:
        return apply_held_moe(p, cfg, x, rows)
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
