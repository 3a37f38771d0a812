"""Operations that MoE training cells need, from the configuration's
shapes, and the device time of the program's named scopes.

As in ``work.py``, counts come from the problem's own sizes (widths,
tokens, the expert budget the configuration states), never from whatever
a program happens to compute.
"""
from __future__ import annotations

from typing import Iterable, Optional

from chipbench import tracefold
from chipbench.reference.deepseek_v2 import budget


def _attn_params(cfg: dict) -> int:
    """Latent attention's projection weights in one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (d * h * (nope + rope) + d * r + d * rope + r * h * nope + r * h * vd
            + h * vd * d)


def active_matmul_params(cfg: dict) -> float:
    """Weights one token is multiplied by on this chip: every layer's
    attention projections, the dense layers' SwiGLU, each MoE layer's
    router and shared experts, the output head, and of the routed experts
    the chip's budget: capacity factor x top_k x held / router_experts of
    one expert's three weights.  The input embedding is a lookup, not a
    product, and does not count."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    routed_share = (cfg["expert_budget"]["device_capacity_factor"]
                    * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                    / cfg["router_experts"])
    moe = (d * cfg["router_experts"] + 3 * d * f * cfg["n_shared_experts"]
           + routed_share * 3 * d * f)
    return (layers * _attn_params(cfg) + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * moe + d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOP per trained token: 6 x the active product weights, plus
    attention's score and value products, 6 x L x S x heads x (q/k head
    width + v head width) (forward and backward, the causal half not
    subtracted, as ``work.train_flops_per_token`` counts them).
    Recomputation is not counted."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (6.0 * active_matmul_params(cfg)
            + 6.0 * cfg["num_hidden_layers"] * seq_len * h * (qk + cfg["v_head_dim"]))


def expert_flops_per_step(cfg: dict, mix: dict) -> float:
    """FLOP of the grouped expert products in one training step: on each
    microbatch and MoE layer, B budget rows through the gate, up and down
    products (2 x B x 3 x d x f), four times: the forward pass, its
    recomputation under full remat, and the backward pass's two products
    per weight."""
    k = cfg["parallel"]["microbatches"]
    rows = budget(cfg, mix["global_batch"] // k * mix["seq_len"])
    forward = 2.0 * rows * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return k * moe_layers * 4 * forward


def scope_device_s(run, scopes: Iterable[str]) -> Optional[float]:
    """Device seconds of the traced window spent in the compiled ops under
    the named scopes (``facts["scope_ops"]``, from the driver), averaged
    over the chips; None where the run recorded no such ops."""
    ops = run.facts.get("scope_ops") or {}
    names = set().union(*(ops.get(s, ()) for s in scopes))
    if not names:
        return None
    fold = run.fold

    def device_ns(dev):
        return tracefold.length(tracefold.union(
            [o for o in dev.ops if tracefold._op_name(o[2]) in names],
            fold.lo, fold.hi))

    seconds = fold.mean_over_devices(device_ns) / 1e9
    return seconds if seconds > 0 else None
