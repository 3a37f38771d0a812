"""Per-architecture smoke tests (deliverable f): each assigned architecture's
REDUCED config runs one forward + one train step + one prefill/decode step on
CPU, asserting output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import SHAPES, ShapeSpec, shape_applicable
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models.model import build_model, count_params_analytic, synthetic_batch
from repro.optim import adamw
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    run = get_smoke_config(arch)
    model = build_model(run, use_kernel=False)
    shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")
    params = model.init(jax.random.key(0))
    opt_cfg = adamw.OptimizerConfig(kind="adamw")
    opt = adamw.init_state(opt_cfg, params)
    step = jax.jit(make_train_step(model, run, opt_cfg))
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(run.model, shape).items()}
    params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"])) and float(metrics["grad_norm"]) > 0
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0
    # loss stays finite over a few steps on refreshed batches
    for s in range(3):
        batch = {k: jnp.asarray(v)
                 for k, v in synthetic_batch(run.model, shape, seed=s + 1).items()}
        params2, opt2, metrics = step(params2, opt2, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    run = get_smoke_config(arch)
    model = build_model(run, use_kernel=False)
    b, s = 2, 16
    params = model.init(jax.random.key(0))
    cache = model.init_cache(b, s + 4, dtype=jnp.float32)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_batch(run.model, ShapeSpec("p", s, b, "prefill")).items()}
    prefill = jax.jit(make_prefill_step(model))
    logits, cache = prefill(params, batch, cache)
    assert logits.shape == (b, 1, run.model.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    decode = jax.jit(make_decode_step(model))
    step_batch = dict(batch)
    if "tokens" in batch:
        step_batch["tokens"] = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    if "embeddings" in batch:
        step_batch["embeddings"] = batch["embeddings"][:, -1:]
    if "labels" in step_batch:
        del step_batch["labels"]
    logits2, cache = decode(params, step_batch, cache, jnp.asarray(s, jnp.int32))
    assert logits2.shape == (b, 1, run.model.vocab_size)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_exact_dims(arch):
    """The FULL configs carry the exact assigned dimensions (checked
    abstractly — full configs are only ever lowered via the dry-run)."""
    run = get_config(arch)
    m = run.model
    expected = {
        "gemma2-2b": (26, 2304, 8, 4, 9216, 256_000),
        "yi-34b": (60, 7168, 56, 8, 20480, 64_000),
        "smollm-135m": (30, 576, 9, 3, 1536, 49_152),
        "stablelm-12b": (40, 5120, 32, 8, 13824, 100_352),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128_256),
        "xlstm-125m": (12, 768, 4, 4, 0, 50_304),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32_000),
        "deepseek-v2-236b": (60, 5120, 128, 128, 12288, 102_400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102_400),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32_000),
    }[arch]
    assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.d_ff,
            m.vocab_size) == expected


PUBLISHED_PARAMS = {
    "gemma2-2b": (2.6e9, 0.15), "yi-34b": (34.4e9, 0.05),
    "smollm-135m": (135e6, 0.1), "stablelm-12b": (12.1e9, 0.1),
    "musicgen-medium": (1.5e9, 0.35), "llama-3.2-vision-11b": (9.8e9, 0.15),
    "xlstm-125m": (125e6, 0.25), "arctic-480b": (480e9, 0.05),
    "deepseek-v2-236b": (236e9, 0.05), "zamba2-7b": (7.0e9, 0.1),
    "deepseek-v2-lite": (15.7e9, 0.05),
}


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_near_published(arch):
    run = get_config(arch)
    n = count_params_analytic(run.model)
    want, tol = PUBLISHED_PARAMS[arch]
    assert abs(n - want) / want < tol, f"{arch}: {n/1e9:.2f}B vs {want/1e9:.2f}B"


def test_shape_grid_applicability():
    """37 runnable cells: long_500k only for sub-quadratic/compressed archs."""
    runnable = 0
    long_ok = set()
    for arch in ARCHS:
        run = get_config(arch)
        for sname, shape in SHAPES.items():
            if shape_applicable(run.model, shape):
                runnable += 1
                if sname == "long_500k":
                    long_ok.add(arch)
    assert long_ok == {"gemma2-2b", "xlstm-125m", "zamba2-7b", "deepseek-v2-236b"}
    assert runnable == 37
