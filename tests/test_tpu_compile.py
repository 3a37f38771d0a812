"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles each program for a v5e that is described, not present.
That catches what interpret mode and the CPU backend hide — Mosaic
lowering of the Pallas kernels, the TPU's 64-bit rewrite of the detection
kernels — at the widths the chip runs (smollm-135m for the kernels, the
10,240-rank ``fleet_day`` deployment for C4D).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.common import jax_compat as jc
from repro.common.jax_compat import enable_x64
from repro.core.jaxsim.kernels import (fused_window_kernel, pad_len,
                                       slow_fold_kernel)
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.splash_attention import causal_attention

# smollm-135m widths (configs/smollm_135m.py) at the smoke's train shape
B, S, H, KV, D, D_MODEL = 8, 4096, 9, 3, 64, 576
# DeepSeek-V2-Lite's latent attention: 16 heads, keys 128 + 64 wide,
# values 128 (its softmax scale with YaRN's temperature)
MLA_H, MLA_D, MLA_DV, MLA_SCALE = 16, 192, 128, 0.1147
# fleet_day: 10,240 ranks; ring channels of stride 1, 3 and 7 (5 divides
# 10,240), ten iterations per window
RANKS = 10_240
GROUPS = 3 * RANKS
SAMPLES_PER_PAIR = 10
HEARTBEATS = 10 * RANKS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_pallas_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    q = _arg((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _arg((B, S, KV, D), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, window=None, scale=D ** -0.5))
    _assert_pallas_kernel(fn.lower(q, kv, kv).compile())


def _attention_grad(scale):
    """The gradient of q, k and v through the training attention kernel."""
    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=scale).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


@pytest.mark.parametrize("h,kv,d,dv,scale", [
    (H, KV, D, D, D ** -0.5),                          # smollm-135m
    (MLA_H, MLA_H, MLA_D, MLA_DV, MLA_SCALE),          # DeepSeek-V2-Lite
], ids=["smollm", "mla"])
def test_splash_attention_grad_compiles_for_v5e(one_chip, h, kv, d, dv, scale):
    args = (_arg((B, S, h, d), jnp.bfloat16, one_chip),
            _arg((B, S, kv, d), jnp.bfloat16, one_chip),
            _arg((B, S, kv, dv), jnp.bfloat16, one_chip))
    _assert_pallas_kernel(_attention_grad(scale).lower(*args).compile())


def _model_attention_grad(monkeypatch, scale, sp_attn=""):
    """The gradient of q, k and v through the model layer's causal
    attention, the platform check steered as a TPU answers it."""
    from repro.kernels import ops
    from repro.models.attention import causal_attention as model_attention
    monkeypatch.setattr(ops, "_kernel_path", lambda use_kernel: use_kernel)

    def loss(q, k, v):
        return jnp.sum(model_attention(q, k, v, scale=scale, train=True,
                                       use_kernel=True, sp_attn=sp_attn
                                       ).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _mesh_grad(monkeypatch, topo, mesh_shape, sp_attn, widths, spec):
    """Compile that gradient at batch 16 on the described 2x2 host, q, k
    and v laid out by ``spec``."""
    h, kv, d, dv, scale = widths
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(mesh_shape),
                             ("data", "model"),
                             axis_types=(jc.AxisType.Auto,) * 2)
    laid = NamedSharding(mesh, spec)
    args = (_arg((16, S, h, d), jnp.bfloat16, laid),
            _arg((16, S, kv, d), jnp.bfloat16, laid),
            _arg((16, S, kv, dv), jnp.bfloat16, laid))
    with jc.set_mesh(mesh):
        return _model_attention_grad(monkeypatch, scale, sp_attn).lower(*args).compile()


SMOLLM_WIDTHS = (H, KV, D, D, D ** -0.5)
MLA_WIDTHS = (MLA_H, MLA_H, MLA_D, MLA_DV, MLA_SCALE)


def test_splash_attention_grad_on_a_data_mesh_gathers_nothing(monkeypatch, topo,
                                                              one_chip):
    """A ``data=4`` mesh over the 2x2 host, batch 16: each chip attends its
    own 4 rows. Without the model layer's ``shard_map`` the compiler
    refuses the kernel or gathers q, k and v to every chip; with it there
    is no all-gather in the program."""
    compiled = _mesh_grad(monkeypatch, topo, (4, 1), "", SMOLLM_WIDTHS,
                          PartitionSpec("data"))
    _assert_pallas_kernel(compiled)
    assert not re.search(r"all-gather", compiled.as_text())


@pytest.mark.parametrize("sp_attn,widths,spec,kernel", [
    # 16 heads: rows over data, heads over model
    ("", MLA_WIDTHS, PartitionSpec("data", None, "model"), True),
    # rows over the whole mesh (``sp_attn="batch"``)
    ("batch", SMOLLM_WIDTHS, PartitionSpec(("data", "model")), True),
    # 3 KV heads do not divide model=2: the jnp path, partitioned by GSPMD
    ("", SMOLLM_WIDTHS, PartitionSpec("data"), False),
], ids=["mla-heads", "smollm-batch", "smollm-heads-undivided"])
def test_attention_grad_on_a_model_mesh(monkeypatch, topo, one_chip, sp_attn,
                                        widths, spec, kernel):
    """A ``data=2, model=2`` mesh, batch 16: the kernel runs where each chip
    can take its own rows and heads, with no all-gather; elsewhere the
    jnp path runs."""
    compiled = _mesh_grad(monkeypatch, topo, (2, 2), sp_attn, widths, spec)
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    if kernel:
        assert not re.search(r"all-gather", text)


def test_flash_attention_traced_window_compiles_for_v5e(one_chip):
    """gemma2-2b widths: the window arrives traced, logits soft-capped."""
    q = _arg((1, S, 8, 256), jnp.bfloat16, one_chip)
    kv = _arg((1, S, 4, 256), jnp.bfloat16, one_chip)
    w = _arg((), jnp.int32, one_chip)
    fn = jax.jit(lambda q, k, v, w: flash_attention_fwd(
        q, k, v, window=w, logit_cap=50.0, scale=256 ** -0.5))
    _assert_pallas_kernel(fn.lower(q, kv, kv, w).compile())


def test_decode_attention_compiles_for_v5e(one_chip):
    q = _arg((B, 1, H, D), jnp.bfloat16, one_chip)
    cache = _arg((B, S, KV, D), jnp.bfloat16, one_chip)
    pos = _arg((), jnp.int32, one_chip)
    fn = jax.jit(lambda q, k, v, p: decode_attention_fwd(
        q, k, v, p, window=None, scale=D ** -0.5))
    _assert_pallas_kernel(fn.lower(q, cache, cache, pos).compile())


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = _arg((B, S, D_MODEL), jnp.bfloat16, one_chip)
    scale = _arg((D_MODEL,), jnp.bfloat16, one_chip)
    _assert_pallas_kernel(jax.jit(rmsnorm_fwd).lower(x, scale).compile())


def test_fused_window_kernel_compiles_for_v5e(one_chip):
    g_pad, m_pad = pad_len(GROUPS), pad_len(SAMPLES_PER_PAIR)
    hb_pad, n_pad = pad_len(HEARTBEATS), pad_len(RANKS)
    with enable_x64():
        compiled = fused_window_kernel.lower(
            _arg((2, g_pad, m_pad), jnp.float64, one_chip),
            _arg((g_pad,), jnp.int64, one_chip),
            _arg((g_pad,), jnp.int64, one_chip),
            _arg((g_pad,), jnp.bool_, one_chip),
            _arg((hb_pad,), jnp.int64, one_chip),
            _arg((hb_pad,), jnp.int64, one_chip),
            _arg((hb_pad,), jnp.bool_, one_chip),
            _arg((n_pad,), jnp.float64, one_chip),
            _arg((), jnp.float64, one_chip),
            n=RANKS, n_pad=n_pad).compile()
    assert compiled.out_info["dmed"].shape == (g_pad,)


def test_slow_fold_kernel_compiles_for_v5e(one_chip):
    g_pad, n_pad = pad_len(GROUPS), pad_len(RANKS)
    f64 = _arg((g_pad,), jnp.float64, one_chip)
    with enable_x64():
        compiled = slow_fold_kernel.lower(
            _arg((g_pad,), jnp.int64, one_chip),
            _arg((g_pad,), jnp.bool_, one_chip),
            f64, f64, f64, f64, f64, f64,
            _arg((), jnp.float64, one_chip),
            _arg((), jnp.float64, one_chip),
            _arg((), jnp.int64, one_chip),
            n=RANKS, n_pad=n_pad).compile()
    assert compiled.out_info["row_sel"].shape == (n_pad,)
