"""Unified decoder LM over heterogeneous layer stacks.

The layer stack is compiled into a list of ``Segment``s.  A segment is a
*unit* pattern repeated ``n_units`` times and executed with a single
``lax.scan`` (parameters stacked on a leading units axis), which keeps HLO
size and compile time bounded for 60-80 layer models.  Heterogeneous
architectures map naturally:

  dense / moe            -> one segment, unit = (block,)
  deepseek (1 dense + N moe) -> two segments
  llama-3.2-vision       -> unit = (self, self, self, cross, self) x 8
  zamba2                 -> unit = (mamba2 x 6, shared_attn) x 13 + tail;
                            shared_attn params are scan-invariant (closure)
  xlstm                  -> unit = (mlstm, mlstm, mlstm, slstm) x 3

Every block supports three modes: ``train`` (full sequence, no cache),
``prefill`` (full sequence, writes cache), ``decode`` (one token + cache).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import (BLOCK_DENSE, BLOCK_MAMBA2, BLOCK_MLSTM,
                                 BLOCK_MOE, BLOCK_SLSTM, ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import (apply_embedding, apply_glu_mlp, apply_rmsnorm,
                                 init_embedding, init_glu_mlp, init_rmsnorm,
                                 logits_from_embedding, softcap, truncated_normal)

BLOCK_CROSS = "cross"
BLOCK_SHARED_ATTN = "shared_attn"


def shard_activations(x):
    """Pin the canonical activation layout (batch over pod x data, features
    unsharded).  Without this, weight specs like the embedding's
    P("model","data") win GSPMD's propagation fight and de-shard the batch —
    a 30x per-device memory regression observed in the dry-run."""
    from repro.models.moe import _maybe_shard
    spec = (("pod", "data"),) + (None,) * (x.ndim - 1)
    return _maybe_shard(x, spec)


# ---------------------------------------------------------------------------
# Segment planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    kinds: Tuple[str, ...]       # block kinds within one unit
    n_units: int
    layer_start: int             # absolute layer index of the first block
    shared: Tuple[int, ...] = () # positions whose params are shared across units


def plan_segments(cfg: ModelConfig) -> List[Segment]:
    L = cfg.n_layers
    if cfg.cross_attn_every:
        # unit = (every-1 self blocks, cross, 1 more self)? Layout: cross at
        # position (every-2) of each unit of length `every` (llama3.2: 3,8,..)
        every = cfg.cross_attn_every
        assert L % every == 0, "vision arch requires n_layers % cross_attn_every == 0"
        unit = tuple([BLOCK_DENSE] * (every - 2) + [BLOCK_CROSS] + [BLOCK_DENSE])
        return [Segment(unit, L // every, 0)]
    if cfg.shared_attn_every and cfg.ssm is not None:
        k = cfg.shared_attn_every
        n_full = L // k
        segs = [Segment(tuple([BLOCK_MAMBA2] * k + [BLOCK_SHARED_ATTN]), n_full, 0,
                        shared=(k,))]
        rem = L - n_full * k
        if rem:
            segs.append(Segment(tuple([BLOCK_MAMBA2] * rem), 1, n_full * k))
        return segs
    if cfg.block_pattern:
        pat = cfg.block_pattern
        assert L % len(pat) == 0
        return [Segment(tuple(pat), L // len(pat), 0)]
    if cfg.moe is not None and cfg.first_k_dense:
        return [
            Segment((BLOCK_DENSE,), cfg.first_k_dense, 0),
            Segment((BLOCK_MOE,), L - cfg.first_k_dense, cfg.first_k_dense),
        ]
    kind = BLOCK_MOE if cfg.moe is not None else BLOCK_DENSE
    return [Segment((kind,), L, 0)]


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype):
    if cfg.mla is not None:
        return attn.init_mla(key, cfg, dtype)
    return attn.init_gqa(key, cfg, dtype)


def init_block(key, cfg: ModelConfig, kind: str, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind in (BLOCK_DENSE, BLOCK_MOE, BLOCK_SHARED_ATTN):
        p = {"ln1": init_rmsnorm(d, dtype), "attn": _init_attn(ks[0], cfg, dtype),
             "ln2": init_rmsnorm(d, dtype)}
        if kind == BLOCK_MOE:
            p["moe"] = moe_mod.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_glu_mlp(ks[1], d, cfg.d_ff, dtype)
        if cfg.post_block_norm:
            p["pn1"] = init_rmsnorm(d, dtype)
            p["pn2"] = init_rmsnorm(d, dtype)
        return p
    if kind == BLOCK_CROSS:
        return {"ln1": init_rmsnorm(d, dtype),
                "xattn": attn.init_cross_attn(ks[0], cfg, dtype),
                "ln2": init_rmsnorm(d, dtype),
                "mlp": init_glu_mlp(ks[1], d, cfg.d_ff, dtype),
                "ffn_gate": jnp.zeros((), dtype)}
    if kind == BLOCK_MAMBA2:
        return {"ln1": init_rmsnorm(d, dtype), "cell": ssm.init_mamba2(ks[0], cfg, dtype)}
    if kind == BLOCK_MLSTM:
        return {"ln1": init_rmsnorm(d, dtype), "cell": ssm.init_mlstm(ks[0], cfg, dtype)}
    if kind == BLOCK_SLSTM:
        return {"ln1": init_rmsnorm(d, dtype), "cell": ssm.init_slstm(ks[0], cfg, dtype)}
    raise ValueError(kind)


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if kind in (BLOCK_DENSE, BLOCK_MOE, BLOCK_SHARED_ATTN):
        if cfg.mla is not None:
            m = cfg.mla
            return attn.MLACache(
                c_kv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                k_rope=jnp.zeros((batch, max_len, m.rope_head_dim), dtype))
        return attn.KVCache(k=jnp.zeros((batch, max_len, hkv, hd), dtype),
                            v=jnp.zeros((batch, max_len, hkv, hd), dtype))
    if kind == BLOCK_CROSS:
        return {}  # vision K/V recomputed from vision_embed (stub frontend)
    if kind == BLOCK_MAMBA2:
        return ssm.init_mamba_cache(cfg, batch, dtype)
    if kind == BLOCK_MLSTM:
        return ssm.init_mlstm_cache(cfg, batch)
    if kind == BLOCK_SLSTM:
        return ssm.init_slstm_cache(cfg, batch)
    raise ValueError(kind)


def apply_block(p, cfg: ModelConfig, kind: str, x, *, mode: str, layer_idx,
                cache=None, pos=None, vision_embed=None, use_kernel=True,
                sp_attn="", rows=None):
    """Returns (x, aux_losses, new_cache).  ``rows`` (B,): each sequence's
    index in the global batch, for the MoE layer's protected rows."""
    aux: Dict[str, jnp.ndarray] = {}
    if kind in (BLOCK_DENSE, BLOCK_MOE, BLOCK_SHARED_ATTN):
        h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
        new_cache = cache
        if cfg.mla is not None:
            if mode == "train":
                a = attn.mla_train(p["attn"], cfg, h, use_kernel=use_kernel)
            elif mode == "prefill":
                a, new_cache = attn.mla_prefill(p["attn"], cfg, h, cache,
                                                use_kernel=use_kernel)
            else:
                a, new_cache = attn.mla_decode(p["attn"], cfg, h, cache, pos)
        else:
            window = attn.layer_window(cfg, layer_idx) if kind != BLOCK_SHARED_ATTN else None
            if mode == "train":
                a = attn.gqa_train(p["attn"], cfg, h, window=window,
                                   use_kernel=use_kernel, sp_attn=sp_attn)
            elif mode == "prefill":
                a, new_cache = attn.gqa_prefill(p["attn"], cfg, h, cache, window=window,
                                                use_kernel=use_kernel, sp_attn=sp_attn)
            else:
                a, new_cache = attn.gqa_decode(p["attn"], cfg, h, cache, pos, window=window,
                                               use_kernel=use_kernel)
        if cfg.post_block_norm:
            a = apply_rmsnorm(p["pn1"], a, cfg.norm_eps)
        x = x + a
        h = apply_rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == BLOCK_MOE:
            ff, aux = moe_mod.apply_moe(p["moe"], cfg, h, rows=rows)
        else:
            ff = apply_glu_mlp(p["mlp"], h, cfg.act)
        if cfg.post_block_norm:
            ff = apply_rmsnorm(p["pn2"], ff, cfg.norm_eps)
        return x + ff, aux, new_cache

    if kind == BLOCK_CROSS:
        h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
        x = x + attn.cross_attn(p["xattn"], cfg, h, vision_embed)
        h = apply_rmsnorm(p["ln2"], x, cfg.norm_eps)
        ff = apply_glu_mlp(p["mlp"], h, cfg.act)
        x = x + jnp.tanh(p["ffn_gate"].astype(x.dtype)) * ff
        return x, aux, cache

    # --- recurrent cells -------------------------------------------------
    h = apply_rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind == BLOCK_MAMBA2:
        fn = ssm.mamba2_decode if mode == "decode" else ssm.mamba2_forward
        out, new_cache = fn(p["cell"], cfg, h, cache if mode != "train" else None)
    elif kind == BLOCK_MLSTM:
        fn = ssm.mlstm_decode if mode == "decode" else ssm.mlstm_forward
        out, new_cache = fn(p["cell"], cfg, h, cache if mode != "train" else None)
    elif kind == BLOCK_SLSTM:
        fn = ssm.slstm_decode if mode == "decode" else ssm.slstm_forward
        out, new_cache = fn(p["cell"], cfg, h, cache if mode != "train" else None)
    else:
        raise ValueError(kind)
    return x + out, aux, (new_cache if mode != "train" else cache)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class LM:
    """Functional decoder LM. All methods are pure (jit/vmap friendly)."""

    def __init__(self, cfg: ModelConfig, param_dtype=jnp.bfloat16,
                 remat: str = "dots", use_kernel: bool = True,
                 unroll: bool = False, sp_attn: str = ""):
        self.cfg = cfg
        self.param_dtype = param_dtype
        self.segments = plan_segments(cfg)
        self.remat = remat
        self.use_kernel = use_kernel
        # sequence-parallel attention activations (see attention._sp_shard)
        self.sp_attn = sp_attn
        # unroll=True replaces scan-over-units with a python loop; used by
        # the roofline to measure exact per-unit FLOPs/bytes/collectives
        # (XLA cost_analysis counts a scan body once, not x trip-count)
        self.unroll = unroll
        # shared positions (e.g. zamba2's shared attention block) are extra
        # applications of one weight set and do not count toward n_layers
        total = sum((len(s.kinds) - len(s.shared)) * s.n_units for s in self.segments)
        assert total == cfg.n_layers, f"segment plan covers {total} != {cfg.n_layers}"

    # ---- init ------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg, dtype = self.cfg, self.param_dtype
        keys = jax.random.split(key, len(self.segments) + 3)
        params: Dict[str, Any] = {}
        if cfg.family != "audio":
            params["embed"] = init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype)
        if cfg.family == "audio" or not cfg.tie_embeddings:
            params["head"] = truncated_normal(
                keys[1], (cfg.d_model, cfg.vocab_size), cfg.d_model ** -0.5, dtype)
        params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
        segs = []
        for si, seg in enumerate(self.segments):
            skey = keys[3 + si]
            unit_p, shared_p = {}, {}
            for pos, kind in enumerate(seg.kinds):
                pkey = jax.random.fold_in(skey, pos)
                if pos in seg.shared:
                    shared_p[str(pos)] = init_block(pkey, cfg, kind, dtype)
                else:
                    unit_keys = jax.random.split(pkey, seg.n_units)
                    unit_p[str(pos)] = jax.vmap(
                        lambda k: init_block(k, cfg, kind, dtype))(unit_keys)
            segs.append({"unit": unit_p, "shared": shared_p})
        params["segments"] = segs
        return params

    # ---- cache -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        caches = []
        for seg in self.segments:
            seg_cache = {}
            for pos, kind in enumerate(seg.kinds):
                one = init_block_cache(cfg, kind, batch, max_len, dtype)
                seg_cache[str(pos)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (seg.n_units,) + a.shape).copy()
                    if seg.n_units > 1 else a[None], one)
            caches.append(seg_cache)
        return caches

    # ---- forward ---------------------------------------------------------
    def _run_segment(self, seg: Segment, seg_params, x, *, mode, cache_seg,
                     pos, vision_embed, rows=None):
        cfg = self.cfg
        use_kernel = self.use_kernel
        shared_p = seg_params["shared"]
        has_cache = cache_seg is not None

        def unit_body(carry, xs):
            x, aux_acc = carry
            x = shard_activations(x)
            unit_p, unit_cache, u = xs
            new_caches = {}
            for pi, kind in enumerate(seg.kinds):
                key = str(pi)
                p = shared_p[key] if pi in seg.shared else jax.tree.map(
                    lambda a: a, unit_p[key])
                layer_idx = seg.layer_start + u * len(seg.kinds) + pi
                c = unit_cache.get(key) if has_cache else None
                x, aux, new_c = apply_block(
                    p, cfg, kind, x, mode=mode, layer_idx=layer_idx,
                    cache=c, pos=pos, vision_embed=vision_embed,
                    use_kernel=use_kernel, sp_attn=self.sp_attn, rows=rows)
                if has_cache:
                    new_caches[key] = new_c
                for k, v in aux.items():
                    aux_acc[k] = aux_acc.get(k, 0.0) + v
            return (x, aux_acc), new_caches

        if mode == "train" and self.remat != "none":
            policy = (jax.checkpoint_policies.nothing_saveable if self.remat == "full"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            unit_body = jax.checkpoint(unit_body, policy=policy)

        aux0 = moe_mod.aux_zeros(cfg.moe) if cfg.moe is not None else {}
        xs = (seg_params["unit"],
              cache_seg if has_cache else {},
              jnp.arange(seg.n_units))
        if self.unroll:
            carry = (x, aux0)
            new_cache_list = []
            for u in range(seg.n_units):
                xs_u = jax.tree.map(lambda a: a[u], xs)
                carry, nc = unit_body(carry, xs_u)
                new_cache_list.append(nc)
            (x, aux) = carry
            new_cache = (jax.tree.map(lambda *a: jnp.stack(a), *new_cache_list)
                         if has_cache else None)
            return x, aux, new_cache
        (x, aux), new_cache = jax.lax.scan(unit_body, (x, aux0), xs)
        return x, aux, (new_cache if has_cache else None)

    def logits_fn(self, params, x):
        """Head projection for arbitrary (..., D) hidden states (post final
        norm). Split out so losses can compute logits in sequence chunks —
        a (B, S, vocab) fp32 tensor for a 256x4096 batch with a 256k vocab
        is ~1 TB and must never be materialised."""
        cfg = self.cfg
        if "head" in params:
            logits = x @ params["head"].astype(x.dtype)
        else:
            logits = logits_from_embedding(params["embed"], x)
        return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)

    def forward(self, params, batch: Dict[str, jnp.ndarray], *, mode: str = "train",
                cache=None, pos=None, head: str = "full"):
        """batch: tokens (B,S) int32 or embeddings (B,S,D); optional
        vision_embed, and optional rows (B,): each sequence's index in the
        global batch (MoE protected rows; the position in the batch else).

        head: "full" -> logits for every position; "last" -> final position
        only (prefill); "none" -> post-norm hidden states (chunked losses).
        Returns (logits_or_hidden, aux, new_cache)."""
        cfg = self.cfg
        if "embeddings" in batch:
            x = batch["embeddings"].astype(self.param_dtype)
        else:
            x = apply_embedding(params["embed"], batch["tokens"],
                                scale_by_sqrt_dim=cfg.embed_scale)
            x = x.astype(self.param_dtype)
        x = shard_activations(x)
        vision_embed = batch.get("vision_embed")
        aux_all: Dict[str, jnp.ndarray] = {}
        new_caches = []
        for si, seg in enumerate(self.segments):
            cache_seg = cache[si] if cache is not None else None
            x, aux, new_c = self._run_segment(
                seg, params["segments"][si], x, mode=mode, cache_seg=cache_seg,
                pos=pos, vision_embed=vision_embed, rows=batch.get("rows"))
            for k, v in aux.items():
                aux_all[k] = aux_all[k] + v if k in aux_all else v
            new_caches.append(new_c)
        x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if head == "none":
            return x, aux_all, (new_caches if cache is not None else None)
        if head == "last":
            x = x[:, -1:]
        logits = self.logits_fn(params, x)
        return logits, aux_all, (new_caches if cache is not None else None)
