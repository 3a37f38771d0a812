"""Attention variants: GQA (full / sliding-window / softcap), MLA, cross-attention.

Three execution paths per variant:
  * ``*_train``   — full-sequence causal attention (query-chunked so a 32k
                    prefill never materialises an S x S score matrix),
  * ``*_prefill`` — same math, additionally returns the KV cache,
  * ``*_decode``  — one new token against an existing KV cache.

On TPU the query-chunked path is replaced by a Pallas kernel: GQA and MLA
attention both go through ``causal_attention`` here, which runs the kernel
under ``shard_map`` on a mesh, and ``repro.kernels.ops.flash_attention``,
which picks the lowering; the jnp code here doubles as the oracle and as
the CPU/dry-run lowering.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import jax_compat as jc
from repro.common.config import MLAConfig, ModelConfig, YarnConfig
from repro.models.layers import apply_rope, rope_freqs, softcap, truncated_normal

NEG_INF = -2.3819763e38  # matches jnp.finfo(f32) order of magnitude w/o inf arithmetic


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------

def causal_window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window) -> jnp.ndarray:
    """(Q, K) bool mask. ``window`` 0/None = full causal; may be a traced scalar."""
    mask = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        w = jnp.asarray(window)
        mask = jnp.logical_and(
            mask, jnp.where(w > 0, k_pos[None, :] > q_pos[:, None] - w, True)
        )
    return mask


def _softmax_attend(q, k, v, mask, logit_cap: float, scale: float):
    """q:(B,Q,H,D) k:(B,K,Hkv,D) v:(B,K,Hkv,Dv) mask:(Q,K) -> (B,Q,H,Dv).

    GQA: H query heads grouped onto Hkv kv heads.
    """
    b, qlen, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    q = q.reshape(b, qlen, hkv, group, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    scores = softcap(scores, logit_cap)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, qlen, h, v.shape[-1])


def chunked_causal_attention(q, k, v, *, window=0, logit_cap: float = 0.0,
                             scale: float, q_chunk: int = 1024) -> jnp.ndarray:
    """Query-chunked attention; memory O(q_chunk * S) instead of O(S^2).

    q: (B, S, H, D); k/v: (B, Sk, Hkv, D*).
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    if s <= q_chunk:
        mask = causal_window_mask(jnp.arange(s), jnp.arange(sk), window)
        return _softmax_attend(q, k, v, mask, logit_cap, scale)
    n_chunks = (s + q_chunk - 1) // q_chunk
    pad = n_chunks * q_chunk - s
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qp = qp.reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)

    k_pos = jnp.arange(sk)

    @jax.checkpoint
    def body(carry, args):
        # rematted: per-chunk (B, H, qc, S) scores are recomputed in the
        # backward pass, not stored as stacked scan residuals
        i, qc = args
        q_pos = i * q_chunk + jnp.arange(q_chunk)
        mask = causal_window_mask(q_pos, k_pos, window)
        out = _softmax_attend(qc, k, v, mask, logit_cap, scale)
        return carry, out

    from repro.common.scan_utils import scan as _scan
    _, outs = _scan(body, None, (jnp.arange(n_chunks), qp))
    outs = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * q_chunk, h, v.shape[-1])
    return outs[:, :s]


def causal_attention(q, k, v, *, scale: float, train: bool, use_kernel: bool,
                     window=None, logit_cap: float = 0.0, sp_attn: str = "",
                     q_chunk: int = 1024):
    """Causal self attention through ``kernels.ops.flash_attention``.

    The splash kernel is a ``pallas_call``, which GSPMD cannot partition:
    on a mesh of more than one device it runs under ``shard_map``, each
    chip on its own rows (over pod x data, or over the whole mesh with
    ``sp_attn="batch"``) and its own heads over ``model``. Where rows or
    heads do not divide so, the jnp path runs, partitioned by GSPMD.
    """
    from repro.kernels import ops as kops
    attend = functools.partial(kops.flash_attention, window=window,
                               logit_cap=logit_cap, scale=scale, train=train,
                               q_chunk=q_chunk)
    mesh = jc.get_abstract_mesh()
    if (mesh.empty or mesh.size == 1 or kops.attention_path(
            q, k, v, window=window, use_kernel=use_kernel, train=train) != "splash"):
        return attend(q, k, v, use_kernel=use_kernel)
    spec = _kernel_spec(mesh, q.shape[0], q.shape[2], k.shape[2], sp_attn)
    if spec is None:
        return attend(q, k, v, use_kernel=False)
    return jc.shard_map(functools.partial(attend, use_kernel=use_kernel),
                        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                        check_vma=False)(q, k, v)


def _kernel_spec(mesh, rows: int, heads: int, kv_heads: int, sp_attn: str):
    """The spec of q, k, v and the output, (B, S, H, D), that gives each
    device of ``mesh`` its own rows and heads; None where they do not
    divide."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import rows_entry
    row_axes = ("pod", "data", "model") if sp_attn == "batch" else ("pod", "data")
    entry = rows_entry(mesh, rows, row_axes)
    split = 1 if entry is None else math.prod(
        mesh.shape[a] for a in ((entry,) if isinstance(entry, str) else entry))
    tp = 1 if sp_attn == "batch" else mesh.shape.get("model", 1)
    if heads % tp or kv_heads % tp or split * tp != mesh.size:
        return None
    return P(entry, None, "model" if tp > 1 else None, None)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jnp.ndarray  # (B, S_max, Hkv, D)
    v: jnp.ndarray  # (B, S_max, Hkv, Dv)


def init_gqa(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal(ks[0], (d, h * hd), d ** -0.5, dtype),
        "wk": truncated_normal(ks[1], (d, hkv * hd), d ** -0.5, dtype),
        "wv": truncated_normal(ks[2], (d, hkv * hd), d ** -0.5, dtype),
        "wo": truncated_normal(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.zeros((hd,), dtype)}
        p["k_norm"] = {"scale": jnp.zeros((hd,), dtype)}
    return p


def _gqa_qkv(p, cfg: ModelConfig, x, positions):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        from repro.models.layers import apply_rmsnorm
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sp_shard(q, k, v, mode: str = "sequence"):
    """Attention-activation resharding (beyond-paper perf levers,
    EXPERIMENTS.md §Perf): when the head count does not divide the tensor
    axis, head-sharded attention is impossible and GSPMD falls back to
    all-gathering the full (B,S,H,D) activations each layer.

    mode="batch": shard the BATCH over the whole mesh (pod x data x model) —
    one sequence per chip on the 256-chip pod: attention is fully local,
    the only cost is a cheap batch reshard in and out (~x/16 bytes).
    mode="sequence": shard S over the tensor axis (kept for the record —
    refuted in Perf iteration 2: GSPMD thrashes layouts of the chunked scan).
    """
    from repro.models.moe import _maybe_shard
    if mode == "batch":
        spec = (("pod", "data", "model"), None, None, None)
        return (_maybe_shard(q, spec), _maybe_shard(k, spec),
                _maybe_shard(v, spec))
    q = _maybe_shard(q, (("pod", "data"), "model", None, None))
    k = _maybe_shard(k, (("pod", "data"), None, None, None))
    v = _maybe_shard(v, (("pod", "data"), None, None, None))
    return q, k, v


def gqa_train(p, cfg: ModelConfig, x, *, window=0, use_kernel: bool = True,
              sp_attn: str = ""):
    """Full-sequence causal self attention. x: (B,S,D) -> (B,S,D)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    if sp_attn:
        q, k, v = _sp_shard(q, k, v, sp_attn)
    out = causal_attention(
        q, k, v, window=window, logit_cap=cfg.attn_logit_softcap,
        scale=cfg.resolved_head_dim ** -0.5, train=True, use_kernel=use_kernel,
        sp_attn=sp_attn)
    return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)


def gqa_prefill(p, cfg: ModelConfig, x, cache: KVCache, *, window=0,
                use_kernel: bool = True, sp_attn: str = ""):
    """Prefill: attend causally and write k/v into the (zero-initialised) cache."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    if sp_attn:
        q, k, v = _sp_shard(q, k, v, sp_attn)
    out = causal_attention(
        q, k, v, window=window, logit_cap=cfg.attn_logit_softcap,
        scale=cfg.resolved_head_dim ** -0.5, train=False, use_kernel=use_kernel,
        sp_attn=sp_attn)
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(cache.k.astype(k.dtype), k, 0, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(cache.v.astype(v.dtype), v, 0, axis=1),
    )
    return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype), new_cache


def gqa_decode(p, cfg: ModelConfig, x, cache: KVCache, pos, *, window=0,
               use_kernel: bool = True):
    """One-token decode. x: (B,1,D); pos: scalar int32 (current length)."""
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q, k, v = _gqa_qkv(p, cfg, x, positions)
    ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), pos, axis=1)
    scale = hd ** -0.5
    from repro.kernels import ops as kops
    out = kops.decode_attention(
        q, ck, cv, pos, window=window, logit_cap=cfg.attn_logit_softcap, scale=scale,
        use_kernel=use_kernel)
    return out.reshape(b, 1, -1) @ p["wo"].astype(x.dtype), KVCache(ck, cv)


def layer_window(cfg: ModelConfig, layer_idx) -> Optional[jnp.ndarray]:
    """Per-layer sliding window (gemma2 alternates local / global). Returns a
    traced scalar usable inside scan (0 = full attention)."""
    if cfg.local_global_alternating:
        return jnp.where(layer_idx % 2 == 0, cfg.sliding_window, 0)
    if cfg.sliding_window:
        return jnp.asarray(cfg.sliding_window)
    return None


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

#: query rows a chunk of MLA's attention on the jnp path: its float32
#: scores for 8 rows x 16 heads x 4,096 keys are 1 GiB a chunk of 512
MLA_Q_CHUNK = 512


class MLACache(NamedTuple):
    c_kv: jnp.ndarray    # (B, S_max, kv_lora)
    k_rope: jnp.ndarray  # (B, S_max, rope_dim)


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": truncated_normal(ks[0], (d, m.kv_lora_rank), d ** -0.5, dtype),
        "w_krope": truncated_normal(ks[1], (d, m.rope_head_dim), d ** -0.5, dtype),
        "w_uk": truncated_normal(ks[2], (m.kv_lora_rank, h * m.nope_head_dim),
                                 m.kv_lora_rank ** -0.5, dtype),
        "w_uv": truncated_normal(ks[3], (m.kv_lora_rank, h * m.v_head_dim),
                                 m.kv_lora_rank ** -0.5, dtype),
        "wo": truncated_normal(ks[4], (h * m.v_head_dim, d), (h * m.v_head_dim) ** -0.5, dtype),
        "kv_norm": {"scale": jnp.zeros((m.kv_lora_rank,), dtype)},
    }
    if m.q_lora_rank:
        p["w_dq"] = truncated_normal(ks[5], (d, m.q_lora_rank), d ** -0.5, dtype)
        p["w_uq"] = truncated_normal(ks[6], (m.q_lora_rank, h * qd), m.q_lora_rank ** -0.5, dtype)
        p["q_norm"] = {"scale": jnp.zeros((m.q_lora_rank,), dtype)}
    else:
        p["w_q"] = truncated_normal(ks[7], (d, h * qd), d ** -0.5, dtype)
    return p


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, y: YarnConfig) -> np.ndarray:
    """YaRN inverse frequencies (``DeepseekV2YarnRotaryEmbedding``): the
    plain frequencies below ``beta_fast`` rotations over the original
    context, those divided by ``factor`` above ``beta_slow``, and a linear
    blend between."""
    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extra = rope_freqs(dim, theta)
    return extra / y.factor * ramp + extra * (1.0 - ramp)


def mla_softmax_scale(m: MLAConfig) -> float:
    """(nope + rope)^-0.5, times YaRN's ``mscale_all_dim`` temperature
    squared where rope scaling is on."""
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    if m.yarn is not None and m.yarn.mscale_all_dim:
        scale *= yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg: ModelConfig):
    """Rope on the rope dims as ``modeling_deepseek.py`` lays them out:
    interleaved pairs are first gathered into halves, then rotated."""
    m = cfg.mla
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    if m.yarn is None:
        return apply_rope(x, positions, cfg.rope_theta)
    y = m.yarn
    out = apply_rope(x, positions, cfg.rope_theta,
                     inv_freq=yarn_inv_freq(d, cfg.rope_theta, y))
    ratio = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    return out if ratio == 1.0 else out * jnp.asarray(ratio, out.dtype)


def _mla_q(p, cfg: ModelConfig, x, positions):
    from repro.models.layers import apply_rmsnorm
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank:
        cq = apply_rmsnorm(p["q_norm"], x @ p["w_dq"].astype(x.dtype), cfg.norm_eps)
        q = (cq @ p["w_uq"].astype(x.dtype)).reshape(b, s, h, qd)
    else:
        q = (x @ p["w_q"].astype(x.dtype)).reshape(b, s, h, qd)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = _mla_rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _mla_ckv(p, cfg: ModelConfig, x, positions):
    from repro.models.layers import apply_rmsnorm
    c_kv = apply_rmsnorm(p["kv_norm"], x @ p["w_dkv"].astype(x.dtype), cfg.norm_eps)
    k_rope = (x @ p["w_krope"].astype(x.dtype))[:, :, None, :]  # single shared head
    k_rope = _mla_rope(k_rope, positions, cfg)[:, :, 0]
    return c_kv, k_rope


def mla_attend(p, cfg: ModelConfig, q_nope, q_rope, c_kv, k_rope, *,
               train: bool, use_kernel: bool = True):
    """Naive (non-absorbed) MLA: materialise per-head K/V from the latent,
    then causal attention through ``kernels.ops`` (the splash kernel on a
    TPU, ``MLA_Q_CHUNK``-row query chunks on the jnp path)."""
    m = cfg.mla
    b, sk = c_kv.shape[:2]
    h = cfg.n_heads
    with jax.named_scope("mla.attend"):
        k_nope = (c_kv @ p["w_uk"].astype(c_kv.dtype)).reshape(b, sk, h, m.nope_head_dim)
        v = (c_kv @ p["w_uv"].astype(c_kv.dtype)).reshape(b, sk, h, m.v_head_dim)
        k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (b, sk, h, m.rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        return causal_attention(q, k, v, scale=mla_softmax_scale(m), train=train,
                                use_kernel=use_kernel, q_chunk=MLA_Q_CHUNK)


def mla_train(p, cfg: ModelConfig, x, *, use_kernel: bool = True):
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(p, cfg, x, positions)
    out = mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, train=True,
                     use_kernel=use_kernel)
    return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)


def mla_prefill(p, cfg: ModelConfig, x, cache: MLACache, *,
                use_kernel: bool = True):
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(p, cfg, x, positions)
    out = mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, train=False,
                     use_kernel=use_kernel)
    new_cache = MLACache(
        c_kv=jax.lax.dynamic_update_slice_in_dim(cache.c_kv.astype(c_kv.dtype), c_kv, 0, 1),
        k_rope=jax.lax.dynamic_update_slice_in_dim(cache.k_rope.astype(k_rope.dtype), k_rope, 0, 1),
    )
    return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype), new_cache


def mla_decode(p, cfg: ModelConfig, x, cache: MLACache, pos):
    """Absorbed-matrix decode: queries projected into the latent space so the
    cache stays (kv_lora + rope) wide — the property that makes MLA's 500k
    cache small."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)          # (B,1,H,*)
    c_kv_t, k_rope_t = _mla_ckv(p, cfg, x, positions)      # (B,1,lora) / (B,1,rope)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_kv_t.astype(cache.c_kv.dtype), pos, 1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, k_rope_t.astype(cache.k_rope.dtype), pos, 1)
    # absorb W_uk into q: q_lat (B,1,H,lora)
    w_uk = p["w_uk"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.nope_head_dim)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk)
    scale = mla_softmax_scale(m)
    scores = jnp.einsum("bqhl,bkl->bhqk", q_lat.astype(jnp.float32), c_kv.astype(jnp.float32))
    scores += jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))
    scores *= scale
    mask = jnp.arange(c_kv.shape[1])[None, None, None, :] <= pos
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkl->bqhl", probs, c_kv.astype(jnp.float32)).astype(x.dtype)
    w_uv = p["w_uv"].astype(x.dtype).reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bqhl,lhd->bqhd", ctx, w_uv)
    out = out.reshape(b, 1, h * m.v_head_dim) @ p["wo"].astype(x.dtype)
    return out, MLACache(c_kv, k_rope)


# ---------------------------------------------------------------------------
# Cross attention (llama-3.2-vision image layers)
# ---------------------------------------------------------------------------

def init_cross_attn(key, cfg: ModelConfig, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dv = cfg.vision_d_model or d
    ks = jax.random.split(key, 5)
    return {
        "wq": truncated_normal(ks[0], (d, h * hd), d ** -0.5, dtype),
        "wk": truncated_normal(ks[1], (dv, hkv * hd), dv ** -0.5, dtype),
        "wv": truncated_normal(ks[2], (dv, hkv * hd), dv ** -0.5, dtype),
        "wo": truncated_normal(ks[3], (h * hd, d), (h * hd) ** -0.5, dtype),
        "gate": jnp.zeros((), dtype),
    }


def cross_attn(p, cfg: ModelConfig, x, vision_embed):
    """x: (B,S,D); vision_embed: (B,Sv,Dv). Tanh-gated cross attention.

    K/V are broadcast from the kv heads to the full query heads before the
    attention einsum: the GQA (hkv, group) reshape would split the head dim
    into factors the 16-way tensor axis cannot shard (8x4 for llama-vision),
    de-sharding the (B, H, S, Sv) score tensor. The broadcast KV is tiny
    (Sv * H * hd) while the sharded scores save GiBs per device."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    sv = vision_embed.shape[1]
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (vision_embed.astype(x.dtype) @ p["wk"].astype(x.dtype)).reshape(b, sv, hkv, hd)
    v = (vision_embed.astype(x.dtype) @ p["wv"].astype(x.dtype)).reshape(b, sv, hkv, hd)
    group = h // hkv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    mask = jnp.ones((s, sv), dtype=bool)
    out = _softmax_attend(q, k, v, mask, 0.0, hd ** -0.5)
    out = out.reshape(b, s, h * hd) @ p["wo"].astype(x.dtype)
    return jnp.tanh(p["gate"].astype(x.dtype)) * out
