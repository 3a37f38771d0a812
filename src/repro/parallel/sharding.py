"""Sharding rules: parameter / batch / cache PartitionSpecs.

Logical axes:
  "fsdp"  -> the ZeRO-3 axis ("data", optionally ("pod","data"))
  "tp"    -> tensor-parallel axis ("model"): attention heads, FFN hidden,
             MoE experts (EP), vocab
  "dp"    -> pure batch axis (("pod","data") on the multi-pod mesh)

Rules are (path-regex, per-dim logical axes).  Every dim is checked for
divisibility against the mesh — a non-dividing dim silently degrades to
replication for that dim, which keeps all 10 architectures (4-head xlstm to
128-head deepseek) compiling on the fixed 16x16 production mesh.
"""
from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import jax_compat as jc

LOGICAL_TO_MESH = {
    "fsdp": ("data",),
    "fsdp_pod": ("pod", "data"),
    "tp": ("model",),
    "dp": ("pod", "data"),
    None: None,
}

# (path regex, logical spec per dim). First match wins. Paths look like
# "segments/0/unit/1/attn/wq" etc. Leading (n_units,) stack dim is dim 0
# for everything under "unit/".
PARAM_RULES: List[Tuple[str, Tuple]] = [
    (r"embed/table$",            ("tp", "fsdp")),
    (r"^head$",                  ("fsdp", "tp")),
    (r"final_norm",              (None,)),
    # --- attention ---
    (r"attn/wq$",                (None, "fsdp", "tp")),
    (r"attn/wk$",                (None, "fsdp", "tp")),
    (r"attn/wv$",                (None, "fsdp", "tp")),
    (r"attn/wo$",                (None, "tp", "fsdp")),
    (r"attn/(q_norm|k_norm)",    (None, None)),
    # --- MLA ---
    (r"attn/w_dkv$",             (None, "fsdp", None)),
    (r"attn/w_krope$",           (None, "fsdp", None)),
    (r"attn/w_uk$",              (None, None, "tp")),
    (r"attn/w_uv$",              (None, None, "tp")),
    (r"attn/w_dq$",              (None, "fsdp", None)),
    (r"attn/w_uq$",              (None, None, "tp")),
    (r"attn/kv_norm",            (None, None)),
    # --- cross attention ---
    (r"xattn/wq$",               (None, "fsdp", "tp")),
    (r"xattn/w[kv]$",            (None, "fsdp", "tp")),
    (r"xattn/wo$",               (None, "tp", "fsdp")),
    (r"xattn/gate$",             (None,)),
    # --- dense MLP ---
    (r"mlp/wi_(gate|up)$",       (None, "fsdp", "tp")),
    (r"mlp/wo$",                 (None, "tp", "fsdp")),
    # --- MoE (experts over tp = EP) ---
    (r"moe/router$",             (None, "fsdp", None)),
    (r"moe/wi_(gate|up)$",       (None, "tp", "fsdp", None)),
    (r"moe/wo$",                 (None, "tp", None, "fsdp")),
    (r"moe/(shared|dense_residual)/wi_(gate|up)$", (None, "fsdp", "tp")),
    (r"moe/(shared|dense_residual)/wo$",           (None, "tp", "fsdp")),
    # --- mamba2 ---
    (r"cell/in_proj$",           (None, "fsdp", "tp")),
    (r"cell/conv_w$",            (None, None, "tp")),
    (r"cell/conv_b$",            (None, "tp")),
    (r"cell/out_proj$",          (None, "tp", "fsdp")),
    (r"cell/(A_log|dt_bias|D)$", (None, "tp")),
    # --- mLSTM / sLSTM ---
    (r"cell/up$",                (None, "fsdp", "tp")),
    (r"cell/w[qkv]$",            (None, "fsdp", "tp")),
    (r"cell/wif$",               (None, "fsdp", None)),
    (r"cell/down$",              (None, "tp", "fsdp")),
    (r"cell/w$",                 (None, "fsdp", "tp")),
    (r"cell/r$",                 (None, None, "tp", None, None)),
    (r"cell/out$",               (None, "fsdp", "tp")),
    (r"cell/(b|if_bias)$",       (None, None)),
    # --- everything else (norm scales, gates, biases) replicated ---
    (r".*",                      None),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _resolve(logical: Optional[str], mesh: Mesh, dim_size: int,
             fsdp_over_pod: bool):
    if logical is None:
        return None
    if logical == "fsdp" and fsdp_over_pod and "pod" in mesh.axis_names:
        logical = "fsdp_pod"
    axes = LOGICAL_TO_MESH[logical]
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    total = int(np.prod([mesh.shape[a] for a in axes]))
    if dim_size % total != 0:
        # try a prefix of the axes (e.g. only "pod" of ("pod","data"))
        for k in range(len(axes) - 1, 0, -1):
            sub = axes[:k]
            t = int(np.prod([mesh.shape[a] for a in sub]))
            if dim_size % t == 0:
                return sub if len(sub) > 1 else sub[0]
        return None
    return axes if len(axes) > 1 else axes[0]


def spec_for_path(path_str: str, shape: Tuple[int, ...], mesh: Mesh,
                  fsdp_over_pod: bool = False,
                  rules: Sequence[Tuple[str, Tuple]] = PARAM_RULES) -> P:
    for pattern, logical in rules:
        if re.search(pattern, path_str):
            if logical is None:
                return P()
            # stacked-unit params may have MORE leading dims than the rule
            # (e.g. vmapped init adds (n_units,)); align the rule to the
            # trailing dims and replicate extra leading dims.
            nl, nd = len(logical), len(shape)
            if nl < nd:
                logical = (None,) * (nd - nl) + tuple(logical)
            elif nl > nd:
                logical = tuple(logical[nl - nd:])
            used: set = set()
            out = []
            for dim, lg in zip(shape, logical):
                r = _resolve(lg, mesh, dim, fsdp_over_pod)
                # one mesh axis may shard only one dim
                flat = (r if isinstance(r, tuple) else (r,)) if r else ()
                if any(a in used for a in flat):
                    out.append(None)
                    continue
                used.update(flat)
                out.append(r)
            return P(*out)
    return P()


ATTN_W_RE = re.compile(r"attn/w[qkvo]$")
MOE_W_RE = re.compile(r"moe/(wi_(gate|up)|wo)$")

# ZeRO-style expert weights: shard the NON-contracted dim over fsdp so GSPMD
# all-gathers the (small) weights instead of all-reducing the (huge)
# partial-sum activations — EXPERIMENTS.md Perf cell 2. (E, D, F) / (E, F, D):
MOE_ZERO_SPEC = (None, "tp", None, "fsdp")


def param_specs(abstract_params, mesh: Mesh, fsdp_over_pod: bool = False,
                attn_zero: bool = False, moe_zero: bool = False):
    """PartitionSpec pytree for a (possibly abstract) parameter tree.

    ``attn_zero``: ZeRO-style 2D sharding for attention projection weights
    (input dim over data x model, no head-dim sharding).  Used when
    n_heads % tp != 0: head-sharded activations cannot divide the tensor
    axis, so GSPMD falls back to all-gathering the (B,S,H,D) activations
    every layer (~1 GiB/layer on yi-34b); gathering the weights instead is
    ~10x cheaper (see EXPERIMENTS.md section Perf)."""
    both = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    total = int(np.prod([mesh.shape[a] for a in both]))

    def visit(path, leaf):
        ps = _path_str(path)
        if attn_zero and ATTN_W_RE.search(ps) and len(leaf.shape) >= 2:
            din = leaf.shape[-2]
            if din % total == 0:
                return P(*((None,) * (len(leaf.shape) - 2) + (both, None)))
        if moe_zero and MOE_W_RE.search(ps) and len(leaf.shape) >= 3:
            rule = MOE_ZERO_SPEC[-len(leaf.shape):]
            used = []
            out = []
            for dim, lg in zip(leaf.shape, rule):
                r = _resolve(lg, mesh, dim, fsdp_over_pod)
                flat = (r if isinstance(r, tuple) else (r,)) if r else ()
                if any(a in used for a in flat):
                    out.append(None)
                    continue
                used.extend(flat)
                out.append(r)
            return P(*out)
        return spec_for_path(ps, leaf.shape, mesh, fsdp_over_pod)
    return jax.tree_util.tree_map_with_path(visit, abstract_params)


def opt_state_specs(abstract_opt, abstract_params, specs_params):
    """An optimizer leaf shaped like its parameter (``m/<param>/mu``) takes
    that parameter's spec, so FSDP shards the moments with the weights;
    any other leaf (step counter, factored or quantised state) is
    replicated."""
    by_path = {
        jax.tree_util.keystr(path): (leaf.shape, spec)
        for (path, leaf), spec in zip(
            jax.tree_util.tree_flatten_with_path(abstract_params)[0],
            jax.tree.leaves(specs_params, is_leaf=lambda x: isinstance(x, P)))}

    def visit(path, leaf):
        # path is ('m', <param path...>, '<state leaf>')
        shape, spec = by_path.get(jax.tree_util.keystr(path[1:-1]), (None, P()))
        return spec if shape == leaf.shape else P()
    return jax.tree_util.tree_map_with_path(visit, abstract_opt)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_specs(abstract_batch, mesh: Mesh):
    """Shard the leading (global batch) dim over pod x data when divisible."""
    dp = batch_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def visit(path, leaf):
        if leaf.ndim >= 1 and total > 1 and leaf.shape[0] % total == 0:
            return P(dp if len(dp) > 1 else dp[0])
        return P()
    return jax.tree_util.tree_map_with_path(visit, abstract_batch)


def cache_specs(abstract_cache, mesh: Mesh):
    """KV caches: (U, B, S, H, D) or (U, B, S, L). Prefer batch over dp;
    shard heads over tp when divisible, else the sequence dim (SP — the
    long-context decode case), else replicate."""
    dp = batch_axes(mesh)
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    tp = mesh.shape.get("model", 1)

    def visit(path, leaf):
        shape = leaf.shape
        if leaf.ndim < 3:
            return P()
        spec: List = [None] * leaf.ndim
        # dim 0 is the stacked-units dim; dim 1 batch; the rest is state
        # (KV: sequence/heads/head_dim; SSM: heads/state dims)
        if shape[1] % dp_total == 0 and dp_total > 1:
            spec[1] = dp if len(dp) > 1 else dp[0]
        if tp > 1:
            # shard the largest tp-divisible state dim on "model": kv-heads
            # when they divide, else the sequence dim (SP, long-context case)
            cands = [(shape[i], i) for i in range(2, leaf.ndim)
                     if shape[i] % tp == 0 and shape[i] >= tp]
            if cands:
                _, i = max(cands)
                spec[i] = "model"
        return P(*spec)
    return jax.tree_util.tree_map_with_path(visit, abstract_cache)


def to_shardings(spec_tree, mesh: Mesh):
    return jc.tree_map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
