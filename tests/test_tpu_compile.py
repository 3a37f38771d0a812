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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.jax_compat import enable_x64
from repro.core.jaxsim.kernels import (fused_window_kernel, pad_len,
                                       slow_fold_kernel)
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd

# smollm-135m widths (configs/smollm_135m.py) at the smoke's train shape
B, S, H, KV, D, D_MODEL = 8, 4096, 9, 3, 64, 576
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
