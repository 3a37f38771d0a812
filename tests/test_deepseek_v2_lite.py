"""DeepSeek-V2-Lite at CPU size: the program against the plain reference
(``chipbench/reference/deepseek_v2.py``), the held-expert layer's share of
the whole layer, its drop rule, and YaRN against the published formula.

All on seeded random weights at ``smoke_config`` widths: MLA with YaRN on
8 rope dimensions, 16 routed experts top-3 with 2 held, 2 shared experts.
"""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.reference import deepseek_v2 as ref  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.layers import apply_glu_mlp  # noqa: E402
from repro.models.model import lm_loss  # noqa: E402
from repro.models.transformer import LM  # noqa: E402

SMOKE = get_smoke_config("deepseek-v2-lite").model
#: 4 rows of 256 tokens: 1,024 tokens, a budget of 384 rows for the 2 held
#: experts of 16 at top-3, so the drop rule is at work
BATCH, SEQ = 4, 256


def ref_cfg(m) -> dict:
    """The reference's configuration (published key names) of a program
    ``ModelConfig``."""
    mla, moe, y = m.mla, m.moe, m.mla.yarn
    return {
        "hidden_size": m.d_model, "num_hidden_layers": m.n_layers,
        "first_k_dense_replace": m.first_k_dense,
        "num_attention_heads": m.n_heads, "kv_lora_rank": mla.kv_lora_rank,
        "qk_rope_head_dim": mla.rope_head_dim,
        "qk_nope_head_dim": mla.nope_head_dim, "v_head_dim": mla.v_head_dim,
        "intermediate_size": m.d_ff, "moe_intermediate_size": moe.d_ff_expert,
        "n_routed_experts": moe.experts_held, "router_experts": moe.num_experts,
        "expert_start": moe.expert_start, "num_experts_per_tok": moe.top_k,
        "n_shared_experts": moe.num_shared_experts,
        "norm_topk_prob": moe.norm_topk_prob,
        "aux_loss_alpha": moe.load_balance_loss,
        "expert_budget": {"device_capacity_factor": moe.capacity_factor,
                          "rows_multiple": moe_mod.BUDGET_TILE,
                          "protected_every": moe.protected_every},
        "vocab_size": m.vocab_size, "rms_norm_eps": m.norm_eps,
        "rope_theta": m.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position_embeddings,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
    }


def to_ref(params) -> dict:
    """The program's parameter tree in the reference's layout."""
    seg = params["segments"]
    return {"embed": params["embed"], "head": params["head"],
            "final_norm": params["final_norm"],
            "dense_layers": seg[0]["unit"]["0"], "moe_layers": seg[1]["unit"]["0"]}


def _flat(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(v, np.float32)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pair():
    """Program (float32) and reference, from the same seeded weights, on a
    batch whose rows are rows 0-3 of the global batch."""
    cfg = ref_cfg(SMOKE)
    model = LM(SMOKE, param_dtype=jnp.float32, remat="none", use_kernel=False)
    params = model.init(jax.random.key(7))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, SMOKE.vocab_size, (BATCH, SEQ)), jnp.int32)
    rows = jnp.arange(BATCH, dtype=jnp.int32)
    return cfg, model, params, tokens, rows


@pytest.mark.parametrize("what", ["init", "logits", "loss", "grad_norms"])
def test_system_matches_reference(pair, what):
    cfg, model, params, tokens, rows = pair
    if what == "init":
        # the reference's key schedule draws the program's bfloat16 weights
        program = LM(SMOKE, param_dtype=jnp.bfloat16).init(jax.random.key(7))
        want = _flat(ref.init_params(cfg, 7))
        got = _flat(to_ref(program))
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
        return
    batch = {"tokens": tokens, "rows": rows}
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            got, aux, _ = model.forward(params, batch)
            x, _, kept, dropped = ref.forward(to_ref(params), tokens, rows, cfg)
            want = ref.ein("bsd,dv->bsv", x, params["head"], "f32")
            assert int(aux["moe_rows_dropped"]) > 0          # drops happened
            assert (int(aux["moe_rows_kept"]), int(aux["moe_rows_dropped"])) == (
                int(kept), int(dropped))
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        elif what == "loss":
            got, _ = lm_loss(model, params, batch)
            want, _ = ref.microbatch_loss(to_ref(params), tokens, rows,
                                          ref.json.dumps(cfg), "f32")
            np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        else:
            g = jax.grad(lambda p: lm_loss(model, p, batch)[0])(params)
            g_ref = jax.grad(lambda p: ref.microbatch_loss(
                p, tokens, rows, ref.json.dumps(cfg), "f32")[0])(to_ref(params))
            got = {k: np.linalg.norm(v) for k, v in _flat(to_ref(g)).items()}
            want = {k: np.linalg.norm(v) for k, v in _flat(g_ref).items()}
            assert set(got) == set(want)
            for k in want:
                assert got[k] == pytest.approx(want[k], rel=1e-3, abs=1e-7), k


# ---------------------------------------------------------------------------
# the held-expert layer
# ---------------------------------------------------------------------------

def _layer(held, start, factor, num_experts=16):
    moe = dataclasses.replace(SMOKE.moe, num_experts=num_experts, experts_held=held,
                              expert_start=start, capacity_factor=factor)
    return dataclasses.replace(SMOKE, moe=moe)


def _share(p, start, held):
    return dict(p, **{w: p[w][start:start + held] for w in ("wi_gate", "wi_up", "wo")})


@pytest.mark.parametrize("seed", [0, 1])
def test_shares_add_up_to_the_uncut_layer(seed):
    """Over all 8 shares of 2 experts, with budgets that drop nothing, the
    shares' outputs, the shared experts counted once, are the uncut
    layer's."""
    whole = _layer(16, 0, 1.0)
    p = moe_mod.init_moe(jax.random.key(seed), whole, jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 100), (2, 64, SMOKE.d_model))
    with jax.default_matmul_precision("highest"):
        full, aux_full = moe_mod.apply_held_moe(p, whole, x)
        shared = apply_glu_mlp(p["shared"], x)
        total = -7 * shared
        kept = 0
        for start in range(0, 16, 2):
            # capacity factor 8 = num_experts / held: room for every row
            out, aux = moe_mod.apply_held_moe(_share(p, start, 2),
                                              _layer(2, start, 8.0), x)
            total = total + out
            kept += int(aux["moe_rows_kept"])
            assert int(aux["moe_rows_dropped"]) == 0
    assert int(aux_full["moe_rows_dropped"]) == 0
    assert kept == int(aux_full["moe_rows_kept"]) == 2 * 64 * SMOKE.moe.top_k
    np.testing.assert_allclose(total, full, rtol=1e-5, atol=1e-5)


def _oracle(p, cfg, x, rows):
    """The drop rule in NumPy: rank the held rows by gate (+1 if the row's
    sequence is protected), stable; keep the budget's first; the kept
    gates times their expert's output, plus the shared experts."""
    m = cfg.moe
    b, s, d = x.shape
    gates, idx, _ = moe_mod.route_topk(p["router"], x, m)
    gates = np.asarray(gates).reshape(b * s, -1)
    local = np.asarray(idx).reshape(b * s, -1) - m.expert_start
    mine = (local >= 0) & (local < m.experts_held)
    protected = np.repeat(np.asarray(rows) % m.protected_every == 0, s)
    priority = np.where(mine, gates + protected[:, None], -1.0).ravel()
    order = np.argsort(-priority, kind="stable")
    budget = moe_mod.budget_rows(b * s, m)
    kept = np.zeros(priority.size, bool)
    kept[order[:budget]] = True
    kept = kept.reshape(mine.shape) & mine
    xt = np.asarray(x, np.float64).reshape(b * s, d)
    out = np.asarray(apply_glu_mlp(p["shared"], x), np.float64).reshape(b * s, d)
    for t, slot in zip(*np.nonzero(kept)):
        e = local[t, slot]
        w = {k: np.asarray(p[k][e], np.float64) for k in ("wi_gate", "wi_up", "wo")}
        g = xt[t] @ w["wi_gate"]
        out[t] += gates[t, slot] * ((g / (1 + np.exp(-g)) * (xt[t] @ w["wi_up"])) @ w["wo"])
    return out.reshape(b, s, d), kept, mine, protected


@pytest.mark.parametrize("case", ["balanced", "skewed", "skewed_protected"])
def test_drop_rule(case):
    """The expert work has the budget's shape whatever the routing; past
    the budget the lowest affinities go first, and rows of protected
    sequences are kept ahead of all others."""
    cfg = _layer(2, 0, 1.0)
    p = moe_mod.init_moe(jax.random.key(3), cfg, jnp.float32)
    if case != "balanced":
        # most tokens route to the held experts 0 and 1: more rows than budget
        p = dict(p, router=p["router"].at[:, :2].add(0.6))
    x = jax.random.normal(jax.random.key(4), (4, 128, SMOKE.d_model))
    rows = jnp.asarray([0, 1, 2, 3] if case != "skewed_protected" else [11, 20, 13, 14])
    budget = moe_mod.budget_rows(4 * 128, cfg.moe)
    # one grouped product shape, B rows, whatever the router does
    jaxpr = jax.make_jaxpr(lambda p: moe_mod.apply_held_moe(p, cfg, x, rows))(p)
    shapes = {tuple(e.invars[0].aval.shape) for e in jaxpr.jaxpr.eqns
              if e.primitive.name == "ragged_dot_general"}
    assert shapes and all(sh[0] == budget for sh in shapes)
    with jax.default_matmul_precision("highest"):
        out, aux = moe_mod.apply_held_moe(p, cfg, x, rows)
    want, kept, mine, protected = _oracle(p, cfg, x, rows)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    assert int(aux["moe_rows_kept"]) == kept.sum() == min(budget, mine.sum())
    assert int(aux["moe_rows_dropped"]) == mine.sum() - kept.sum()
    if case == "balanced":
        assert aux["moe_rows_dropped"] == 0
        return
    assert int(aux["moe_rows_dropped"]) > 0
    gates = np.asarray(moe_mod.route_topk(p["router"], x, cfg.moe)[0]).reshape(kept.shape)
    free = mine & ~protected[:, None]
    # every dropped unprotected row has a lower gate than every kept one
    assert gates[free & ~kept].max() <= gates[free & kept].min()
    if case == "skewed_protected":
        assert kept[mine & protected[:, None]].all()       # rows 20: protected
        assert (mine & protected[:, None]).any()


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _published_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``'s inv_freq."""
    def find_dim(n):
        return (dim * math.log(orig / (n * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    freq_extra, freq_inter = 1.0 / (base ** pos), 1.0 / (factor * base ** pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


@pytest.mark.parametrize("part", ["inv_freq", "softmax_scale", "rope_layout"])
def test_yarn_against_the_published_formula(part):
    full = get_smoke_config("deepseek-v2-lite")
    from repro.configs import get_config
    m = get_config("deepseek-v2-lite").model.mla
    y = m.yarn
    if part == "inv_freq":
        for dim in (m.rope_head_dim, full.model.mla.rope_head_dim):
            want = _published_inv_freq(dim, 10000, y.factor,
                                       y.original_max_position_embeddings,
                                       y.beta_fast, y.beta_slow)
            got = attn.yarn_inv_freq(dim, 10000.0, y)
            np.testing.assert_allclose(got, want, rtol=1e-6)
            # blended: the first dims keep their frequency, the last are /40
            assert got[0] == pytest.approx(want[0]) and got[-1] == pytest.approx(
                1.0 / (40 * 10000 ** ((dim - 2) / dim)), rel=1e-6)
    elif part == "softmax_scale":
        mscale = 0.1 * 0.707 * math.log(40) + 1.0
        assert attn.mla_softmax_scale(m) == pytest.approx(192 ** -0.5 * mscale ** 2)
        assert mscale ** 2 == pytest.approx(1.59, abs=5e-3)
    else:
        # the program's rope on the rope dims equals the published
        # de-interleave + rotate_half (the reference's transcription)
        cfg = ref_cfg(SMOKE)
        x = jax.random.normal(jax.random.key(5), (2, 64, 3, SMOKE.mla.rope_head_dim))
        got = attn._mla_rope(x, jnp.arange(64)[None, :], SMOKE)
        np.testing.assert_allclose(got, ref._rope(x, cfg), rtol=1e-5, atol=1e-5)
