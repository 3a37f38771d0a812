"""Plain float32 reference of DeepSeek-V2-Lite's training steps, cut as the
chip benchmark runs it (``configs/deepseek-v2-lite.json``): straightforward
``jax.numpy`` at ``jax.default_matmul_precision("highest")``, with no
kernels, no grouped products and no sort of rows onto a budget, and the
optimizer in NumPy on the host.

The model follows the published ``modeling_deepseek.py``:

* multi-head latent attention with full-rank queries: per head a query of
  ``qk_nope_head_dim`` + ``qk_rope_head_dim``; keys and values from a
  ``kv_lora_rank`` latent after RMSNorm, the rope part of the key shared by
  the heads; the rope dimensions' interleaved pairs gathered into halves
  and rotated (``rotate_half``) at YaRN's blended inverse frequencies, and
  the softmax scale ``q_head_dim ** -0.5`` times ``mscale(factor,
  mscale_all_dim) ** 2``;
* the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others an MoE layer: softmax over
  ``router_experts`` float32 logits, the top ``num_experts_per_tok`` gates
  not renormalised (``norm_topk_prob`` false, ``routed_scaling_factor``
  1), routed experts and ``n_shared_experts`` shared experts (one SwiGLU
  of ``n_shared_experts * moe_intermediate_size``), and the per-sequence
  balance loss (``seq_aux``) times ``aux_loss_alpha``, added to the loss
  for every MoE layer;
* an untied output head; the loss is the mean next-token cross entropy
  over every position but the last, plus the balance losses.

The cut and the training recipe, as the configuration states them:

* this chip holds routed experts [``expert_start``, ``expert_start`` +
  ``n_routed_experts``) of ``router_experts``; the others' part of each
  token's output is left out (it is computed on the other chips);
* in each forward pass over a microbatch of T tokens, the (token, expert)
  rows routed to the held experts compete for B = ceil(capacity factor *
  T * k * held / router_experts) rows, rounded up to ``rows_multiple``:
  ranked by gate affinity plus one for rows of protected sequences (global
  batch index ``% protected_every == 0``), ties to the earlier row (token
  order, then the top-k order); rows past B are dropped.  Here that is a
  0/1 mask over the (token, slot) pairs, and every held expert runs on
  every token, weighted by its kept gates;
* the vocabulary is the configuration's slice (ids are drawn from it).

Departures from the published model, each the configuration as run:

* RMSNorm multiplies by ``1 + scale`` with ``scale`` starting at zero (the
  published weight starting at one: the same function);
* weights start from a truncated normal (two standard deviations) of
  standard deviation ``fan_in ** -0.5``, drawn from ``--seed`` with the
  program's key schedule (``init_params``), and are held as bfloat16
  values, the router in float32; each update is computed in float32 and
  rounded to the parameter's type;
* the optimizer is AdamW with global-norm clipping and linear warm-up, in
  NumPy float32 on the host.

``ein`` is the one matrix product every layer uses; ``precision="fp8"``
rounds both of its operands to float8 (e4m3), the control one step below
the configuration's bfloat16.
"""
from __future__ import annotations

import json
import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.smollm import batch_tokens, to_bf16  # noqa: F401

#: query rows a block of the attention (the scores of one block fit)
ATTN_BLOCK = 256


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _tn(key, shape, std, bf16=True):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return to_bf16(x) if bf16 else x


def _glu_init(key, d, ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi_gate": _tn(k1, (d, ff), d ** -0.5),
            "wi_up": _tn(k2, (d, ff), d ** -0.5),
            "wo": _tn(k3, (ff, d), ff ** -0.5)}


def _block_init(key, cfg, moe: bool):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 8)
    p = {"ln1": {"scale": jnp.zeros((d,), jnp.float32)},
         "attn": {"w_dkv": _tn(a[0], (d, r), d ** -0.5),
                  "w_krope": _tn(a[1], (d, rope), d ** -0.5),
                  "w_uk": _tn(a[2], (r, h * nope), r ** -0.5),
                  "w_uv": _tn(a[3], (r, h * vd), r ** -0.5),
                  "wo": _tn(a[4], (h * vd, d), (h * vd) ** -0.5),
                  "kv_norm": {"scale": jnp.zeros((r,), jnp.float32)},
                  "w_q": _tn(a[7], (d, h * (nope + rope)), d ** -0.5)},
         "ln2": {"scale": jnp.zeros((d,), jnp.float32)}}
    if moe:
        f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        m = jax.random.split(ks[1], 6)
        p["moe"] = {"router": _tn(m[0], (d, cfg["router_experts"]), d ** -0.5,
                                  bf16=False),
                    "wi_gate": _tn(m[1], (held, d, f), d ** -0.5),
                    "wi_up": _tn(m[2], (held, d, f), d ** -0.5),
                    "wo": _tn(m[3], (held, f, d), f ** -0.5),
                    "shared": _glu_init(m[4], d, f * cfg["n_shared_experts"])}
    else:
        p["mlp"] = _glu_init(ks[1], d, cfg["intermediate_size"])
    return p


def init_params(cfg: dict, seed: int) -> Dict:
    """The configuration's initial weights for ``seed``.

    Key schedule: the seed's key splits into five; the first draws the
    embedding, the second the head, the fourth (folded with 0) one key per
    dense layer and the fifth (folded with 0) one per MoE layer.  A layer's
    key splits into four: the first into eight (query, latent, rope key,
    key and value up-projections and output at 7, 0, 1, 2, 3, 4), the
    second into the SwiGLU's three or the MoE layer's six (router, gate, up,
    down, shared)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    keys = jax.random.split(jax.random.key(seed), 5)

    def stack(key, n, moe):
        ks = jax.random.split(jax.random.fold_in(key, 0), n)
        return jax.vmap(lambda k: _block_init(k, cfg, moe))(ks)

    return {"embed": {"table": _tn(keys[0], (v, d), d ** -0.5)},
            "head": _tn(keys[1], (d, v), d ** -0.5),
            "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
            "dense_layers": stack(keys[3], n_dense, False),
            "moe_layers": stack(keys[4], cfg["num_hidden_layers"] - n_dense, True)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def ein(spec, a, b, precision: str):
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _glu(p, x, precision):
    g = ein("...d,df->...f", x, p["wi_gate"], precision)
    u = ein("...d,df->...f", x, p["wi_up"], precision)
    return ein("...f,fd->...d", jax.nn.silu(g) * u, p["wo"], precision)


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg: dict):
    """``DeepseekV2YarnRotaryEmbedding``: the inverse frequencies and the
    cos/sin magnitude."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    orig, factor = rs["original_max_position_embeddings"], rs["factor"]

    def correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    exponents = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra = 1.0 / (base ** exponents)
    freq_inter = 1.0 / (factor * base ** exponents)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    mscale = (_yarn_get_mscale(factor, rs["mscale"])
              / _yarn_get_mscale(factor, rs["mscale_all_dim"]))
    return inv_freq.astype(np.float32), mscale


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs.get("mscale_all_dim"):
        scale *= _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, cfg):
    """x: (B, S, H, D): pairs (2i, 2i+1) gathered into halves, then
    ``x * cos + rotate_half(x) * sin``."""
    b, s, h, d = x.shape
    inv_freq, mscale = yarn(cfg)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    emb = jnp.concatenate([freqs, freqs], -1)
    cos = (jnp.cos(emb) * mscale)[None, :, None, :]
    sin = (jnp.sin(emb) * mscale)[None, :, None, :]
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(3, 4).reshape(b, s, h, d)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def _attention(q, k, v, scale, precision):
    """Causal softmax attention, ``ATTN_BLOCK`` query rows at a time."""
    b, s, h, _ = q.shape
    blk = min(ATTN_BLOCK, s)
    nb = s // blk
    qb = q.reshape(b, nb, blk, h, q.shape[-1]).swapaxes(0, 1)
    k_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        i, qi = args
        scores = ein("bqhd,bkhd->bhqk", qi, k, precision) * scale
        q_pos = i * blk + jnp.arange(blk)
        scores = jnp.where(k_pos[None, :] <= q_pos[:, None], scores, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision)

    out = jax.lax.map(one, (jnp.arange(nb), qb))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def _mla(p, x, cfg, precision):
    b, s, _ = x.shape
    h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = ein("bsd,de->bse", x, p["w_q"], precision).reshape(b, s, h, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg)
    c = _rmsnorm(ein("bsd,dr->bsr", x, p["w_dkv"], precision),
                 p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    k_pe = _rope(ein("bsd,dr->bsr", x, p["w_krope"], precision)[:, :, None], cfg)
    k_nope = ein("bsr,re->bse", c, p["w_uk"], precision).reshape(b, s, h, nope)
    v = ein("bsr,re->bse", c, p["w_uv"], precision).reshape(b, s, h, vd)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    out = _attention(q, k, v, softmax_scale(cfg), precision)
    return ein("bse,ed->bsd", out.reshape(b, s, h * vd), p["wo"], precision)


def budget(cfg: dict, tokens: int) -> int:
    """Rows the held experts keep in a forward pass over ``tokens`` tokens."""
    eb = cfg["expert_budget"]
    rows = math.ceil(eb["device_capacity_factor"] * tokens
                     * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                     / cfg["router_experts"])
    m = eb["rows_multiple"]
    return -(-rows // m) * m


def route(router, y, rows, cfg, precision):
    """Gates of the kept (token, held expert) rows as a (B, S, held) weight
    matrix, the balance loss, and the kept and dropped row counts."""
    b, s, d = y.shape
    t, k = b * s, cfg["num_experts_per_tok"]
    e, held, e0 = cfg["router_experts"], cfg["n_routed_experts"], cfg["expert_start"]
    probs = jax.nn.softmax(ein("td,de->te", y.reshape(t, d), router, precision), -1)
    gates, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    # seq_aux: per sequence, the selection counts against the mean scores
    ce = jnp.sum(jax.nn.one_hot(idx, e), 1).reshape(b, s, e).sum(1) / (s * k / e)
    aux = jnp.mean(jnp.sum(ce * probs.reshape(b, s, e).mean(1), -1)) * cfg["aux_loss_alpha"]
    local = idx - e0
    mine = (local >= 0) & (local < held)
    every = cfg["expert_budget"]["protected_every"]
    protected = (rows % every == 0) if every else jnp.zeros((b,), bool)
    bonus = jnp.repeat(protected, s)[:, None].astype(jnp.float32)
    priority = jnp.where(mine, jax.lax.stop_gradient(gates) + bonus, -1.0).reshape(t * k)
    order = jnp.argsort(-priority, stable=True)
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(jnp.arange(t * k, dtype=jnp.int32))
    kept = mine & (rank.reshape(t, k) < budget(cfg, t))
    weights = jnp.einsum("tk,tkh->th", jnp.where(kept, gates, 0.0),
                         jax.nn.one_hot(local, held))
    n_kept = jnp.sum(kept)
    return (weights.reshape(b, s, held), aux, n_kept, jnp.sum(mine) - n_kept)


def _routed(p, y, weights, precision):
    """One sequence: every held expert on every token, weighted; plus the
    shared experts."""
    def expert(out, e):
        w = {"wi_gate": e[0], "wi_up": e[1], "wo": e[2]}
        return out + e[3][:, None] * _glu(w, y, precision), None

    experts = (p["wi_gate"], p["wi_up"], p["wo"], weights.T)
    return jax.lax.scan(expert, _glu(p["shared"], y, precision), experts)[0]


def _layer(x, p, rows, cfg, precision, moe: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(p["attn"], _rmsnorm(x, p["ln1"]["scale"], eps), cfg, precision)
    y = _rmsnorm(x, p["ln2"]["scale"], eps)
    # the feed-forward one sequence at a time, so its activations fit
    if not moe:
        ffn = jax.lax.map(jax.checkpoint(lambda yr: _glu(p["mlp"], yr, precision)), y)
        return x + ffn, (0.0, 0, 0)
    weights, aux, kept, dropped = route(p["moe"]["router"], y, rows, cfg, precision)
    ffn = jax.lax.map(jax.checkpoint(lambda a: _routed(p["moe"], *a, precision)),
                      (y, weights))
    return x + ffn, (aux, kept, dropped)


def forward(params, tokens, rows, cfg: dict, precision: str = "f32"):
    """tokens (B, S) of the sequences ``rows`` of the global batch -> the
    hidden states after the final RMSNorm, and the summed balance loss,
    kept rows and dropped rows of the MoE layers."""
    x = params["embed"]["table"][tokens]

    def run(x, layers, moe):
        def body(carry, p):
            x, aux, kept, dropped = carry
            x, (a, k, d) = jax.checkpoint(
                lambda x, p: _layer(x, p, rows, cfg, precision, moe))(x, p)
            return (x, aux + a, kept + k, dropped + d), None
        zero = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32))
        return jax.lax.scan(body, zero, layers)[0]

    x, _, _, _ = run(x, params["dense_layers"], False)
    x, aux, kept, dropped = run(x, params["moe_layers"], True)
    return (_rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"]),
            aux, kept, dropped)


def microbatch_loss(params, tokens, rows, cfg_json: str, precision: str):
    """The loss of one microbatch and (summed token NLL, balance loss, kept
    rows, dropped rows)."""
    x, aux, kept, dropped = forward(params, tokens, rows, json.loads(cfg_json),
                                    precision)

    @jax.checkpoint
    def row_nll(args):
        xr, tr = args
        logp = jax.nn.log_softmax(ein("sd,dv->sv", xr[:-1], params["head"], precision), -1)
        return -jnp.sum(jnp.take_along_axis(logp, tr[1:, None], -1))

    nll = jnp.sum(jax.lax.map(row_nll, (x, tokens)))
    b, s = tokens.shape
    return nll / (b * (s - 1)) + aux, (nll, aux, kept, dropped)


@partial(jax.jit, static_argnames=("cfg_json", "precision"))
def _microbatch_grad(params, tokens, rows, cfg_json, precision):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(microbatch_loss, has_aux=True)(
            params, tokens, rows, cfg_json, precision)


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------

def _flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flat(v, name + "/"))
        else:
            out[name] = v
    return out


def _unflat(flat):
    tree: Dict = {}
    for name, v in flat.items():
        node = tree
        *parents, leaf = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def _round_bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(jnp.bfloat16).astype(np.float32)


def train_steps(cfg: dict, opt: dict, seed: int, batches: List[np.ndarray],
                microbatches: int, precision: str = "f32") -> dict:
    """Follow the configuration's first ``len(batches)`` steps from ``seed``.

    Each step averages the gradients of its ``microbatches`` microbatches
    (the global batch's rows in order, each row's global index deciding
    whether it is protected).  Returns each step's loss of its last
    microbatch (the step reports that one), the per-leaf norms of the first
    gradient as the optimizer gets it (after clipping), the per-leaf norms
    of the parameters' change over all the steps, and each step's kept and
    dropped expert rows."""
    cfg_json = json.dumps(cfg, sort_keys=True)
    flat = {k: np.asarray(v) for k, v in _flat(init_params(cfg, seed)).items()}
    p0 = {k: v.copy() for k, v in flat.items()}
    mu = {k: np.zeros_like(v) for k, v in flat.items()}
    nu = {k: np.zeros_like(v) for k, v in flat.items()}
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    losses, first_grad, kept_rows, dropped_rows = [], None, [], []
    for step, tokens in enumerate(batches):
        b = tokens.shape[0]
        mb = b // microbatches
        grads = {k: np.zeros_like(v) for k, v in flat.items()}
        params = jax.device_put(_unflat(flat))
        kept = dropped = 0
        for i in range(microbatches):
            rows = jnp.arange(i * mb, (i + 1) * mb, dtype=jnp.int32)
            (loss, (_, _, k, d)), g = _microbatch_grad(
                params, jnp.asarray(tokens[i * mb:(i + 1) * mb]), rows, cfg_json,
                precision)
            for name, leaf in _flat(jax.device_get(g)).items():
                grads[name] += leaf
            kept, dropped = kept + int(k), dropped + int(d)
            del g
        del params
        losses.append(float(loss))
        kept_rows.append(kept)
        dropped_rows.append(dropped)
        grads = {k: v / microbatches for k, v in grads.items()}
        norm = np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                           for g in grads.values()))
        scale = np.float32(min(1.0, opt["grad_clip_norm"] / max(norm, 1e-9)))
        lr = opt["learning_rate"] * min(step + 1, opt["warmup_steps"]) / opt["warmup_steps"]
        t = step + 1.0
        for k, g in grads.items():
            g = g * scale
            mu[k] = b1 * mu[k] + (1 - b1) * g
            nu[k] = b2 * nu[k] + (1 - b2) * g * g
            u = (mu[k] / (1 - b1 ** t)) / (np.sqrt(nu[k] / (1 - b2 ** t)) + eps)
            new = flat[k] - np.float32(lr) * (u + opt["weight_decay"] * flat[k])
            # the router is held in float32, every other weight in bfloat16
            flat[k] = new if k.endswith("moe/router") else _round_bf16(new)
            grads[k] = g
        if first_grad is None:
            first_grad = {k: float(np.linalg.norm(g.ravel())) for k, g in grads.items()}
    change = {k: float(np.linalg.norm((flat[k] - p0[k]).ravel())) for k in flat}
    return {"losses": losses, "first_grad": first_grad, "change": change,
            "rows_kept": kept_rows, "rows_dropped": dropped_rows}
