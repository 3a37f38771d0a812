"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd

RNG = np.random.default_rng(0)


def _qkv(b, s, h, hkv, d, dtype):
    q = jnp.asarray(RNG.normal(0, 1, (b, s, h, d)), dtype)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), dtype)
    return q, k, v


FLASH_CASES = [
    # (b, s, h, hkv, d), window, cap, dtype, blocks
    ((2, 256, 4, 2, 64), None, 0.0, jnp.float32, 128),
    ((1, 512, 8, 4, 64), 128, 0.0, jnp.float32, 128),
    ((2, 256, 4, 1, 32), None, 50.0, jnp.float32, 64),
    ((1, 256, 2, 2, 128), 100, 30.0, jnp.bfloat16, 128),
    ((1, 384, 6, 2, 64), 64, 0.0, jnp.float32, 128),
    ((3, 128, 8, 8, 64), None, 0.0, jnp.bfloat16, 64),
]


@pytest.mark.parametrize("dims,window,cap,dtype,block", FLASH_CASES)
def test_flash_attention_matches_oracle(dims, window, cap, dtype, block):
    b, s, h, hkv, d = dims
    q, k, v = _qkv(b, s, h, hkv, d, dtype)
    scale = d ** -0.5
    out = flash_attention_fwd(q, k, v, window=window, logit_cap=cap, scale=scale,
                              block_q=block, block_k=block, interpret=True)
    want = ref.flash_attention(q, k, v, window=window, logit_cap=cap, scale=scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_traced_window():
    """gemma2 alternates local/global inside a scanned stack: the window
    reaches the kernel as a traced scalar."""
    q, k, v = _qkv(1, 256, 4, 2, 64, jnp.float32)

    def f(w):
        return flash_attention_fwd(q, k, v, window=w, logit_cap=0.0,
                                   scale=0.125, block_q=128, block_k=128,
                                   interpret=True)

    out = jax.jit(f)(jnp.asarray(64, jnp.int32))
    want = ref.flash_attention(q, k, v, window=64, logit_cap=0.0, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


DECODE_CASES = [
    ((2, 1024, 8, 2, 64), 700, None, 0.0, jnp.float32),
    ((1, 512, 4, 4, 128), 100, 64, 50.0, jnp.bfloat16),
    ((2, 2048, 16, 2, 64), 2000, None, 0.0, jnp.float32),
    ((4, 256, 4, 1, 32), 0, None, 0.0, jnp.float32),      # first token
]


@pytest.mark.parametrize("dims,pos,window,cap,dtype", DECODE_CASES)
def test_decode_attention_matches_oracle(dims, pos, window, cap, dtype):
    b, s, h, hkv, d = dims
    q = jnp.asarray(RNG.normal(0, 1, (b, 1, h, d)), dtype)
    kc = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), dtype)
    vc = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, d)), dtype)
    scale = d ** -0.5
    out = decode_attention_fwd(q, kc, vc, pos, window=window, logit_cap=cap,
                               scale=scale, block_k=256, interpret=True)
    want = ref.decode_attention(q, kc, vc, pos, window=window, logit_cap=cap,
                                scale=scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("shape,dtype", [
    ((4, 37, 96), jnp.float32),
    ((512, 1024), jnp.bfloat16),
    ((2, 3, 5, 256), jnp.float32),
])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = jnp.asarray(RNG.normal(0, 1, shape), dtype)
    sc = jnp.asarray(RNG.normal(0, 0.1, shape[-1:]), dtype)
    out = rmsnorm_fwd(x, sc, interpret=True)
    want = ref.rmsnorm(x, sc)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_chunked_attention_matches_dense_oracle():
    """The CPU/dry-run lowering (query-chunked) against the dense oracle."""
    from repro.models.attention import chunked_causal_attention
    q, k, v = _qkv(2, 300, 4, 2, 32, jnp.float32)
    out = chunked_causal_attention(q, k, v, window=None, logit_cap=0.0,
                                   scale=0.125, q_chunk=64)
    want = ref.flash_attention(q, k, v, window=None, logit_cap=0.0, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ops_dispatch_cpu_fallback():
    from repro.kernels import ops
    q, k, v = _qkv(1, 128, 4, 2, 32, jnp.float32)
    out = ops.flash_attention(q, k, v, window=None, logit_cap=0.0, scale=0.125)
    want = ref.flash_attention(q, k, v, window=None, logit_cap=0.0, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ops_refuses_unblockable_sequence():
    """On a TPU the kernel path errors instead of quietly taking jnp."""
    from repro.kernels import ops
    assert ops._largest_block(384) == 128
    with pytest.raises(ValueError, match="use_kernel=False"):
        ops._largest_block(300)
