"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.splash_attention import causal_attention

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
    out = ops.flash_attention(q, k, v, window=None, logit_cap=0.0, scale=0.125,
                              train=True)
    want = ref.flash_attention(q, k, v, window=None, logit_cap=0.0, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ops_refuses_unblockable_sequence():
    """On a TPU the kernel path errors instead of quietly taking jnp."""
    from repro.kernels import ops
    assert ops._largest_block(384) == 128
    with pytest.raises(ValueError, match="use_kernel=False"):
        ops._largest_block(300)


#: DeepSeek-V2-Lite's latent-attention scale: (128 + 64)^-0.5 times YaRN's
#: temperature squared (factor 40, mscale_all_dim 0.707)
MLA_SCALE = 192 ** -0.5 * (0.1 * 0.707 * np.log(40.0) + 1.0) ** 2

SPLASH_CASES = {
    # (b, s, h, hkv, d, dv), scale
    "gqa-9/3x64-s256": ((2, 256, 9, 3, 64, 64), 64 ** -0.5),
    "gqa-9/3x64-s512": ((2, 512, 9, 3, 64, 64), 64 ** -0.5),
    "mla-16/16x192/128-s256": ((1, 256, 16, 16, 192, 128), MLA_SCALE),
    "mla-16/16x192/128-s512": ((1, 512, 16, 16, 192, 128), MLA_SCALE),
}


def _rel_gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("case", list(SPLASH_CASES))
def test_splash_attention_matches_oracles(case):
    """The training kernel (interpret mode), forward and the gradients of
    q, k and v, against the dense oracle and the chunked jnp lowering, in
    bf16 as the model runs it."""
    from repro.models.attention import chunked_causal_attention
    (b, s, h, hkv, d, dv), scale = SPLASH_CASES[case]
    q, k, _ = _qkv(b, s, h, hkv, d, jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, hkv, dv)), jnp.bfloat16)
    w = jnp.asarray(RNG.normal(0, 1, (b, s, h, dv)), jnp.float32)
    fns = {
        "splash": functools.partial(causal_attention, scale=scale, interpret=True),
        "ref": functools.partial(ref.flash_attention, scale=scale),
        "chunked": functools.partial(chunked_causal_attention, window=None,
                                     scale=scale, q_chunk=128),
    }
    got = {}
    for name, fn in fns.items():
        def loss(q, k, v, fn=fn):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)
        got[name] = (fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    out, grads = got["splash"]
    assert out.shape == (b, s, h, dv) and out.dtype == jnp.bfloat16
    for oracle in ("ref", "chunked"):
        want_out, want_grads = got[oracle]
        assert _rel_gap(out, want_out) < 2e-2
        for g, want in zip(grads, want_grads):
            assert g.shape == want.shape and g.dtype == want.dtype
            assert _rel_gap(g, want) < 2e-2


@pytest.mark.parametrize("s,blk", [(4096, 512), (1024, 512), (384, 384),
                                   (640, 128), (1280, 256)])
def test_splash_block_size(s, blk):
    from repro.kernels.splash_attention import block_size
    assert block_size(s) == blk


def _spy_kernels(monkeypatch):
    """Steer the platform check as a TPU answers it and record which
    lowering each call takes; the kernels run in interpret mode in place
    of the chip."""
    from repro.kernels import flash_attention as fa_mod
    from repro.kernels import ops
    from repro.kernels import splash_attention as sa_mod
    from repro.models import attention as attn_mod
    called = []

    def spy(name, fn, **extra):
        def wrapped(*a, **kw):
            called.append(name)
            return fn(*a, **extra, **kw)
        return wrapped

    monkeypatch.setattr(ops, "_kernel_path", lambda use_kernel: use_kernel)
    monkeypatch.setattr(sa_mod, "causal_attention",
                        spy("splash", sa_mod.causal_attention, interpret=True))
    monkeypatch.setattr(fa_mod, "flash_attention_fwd",
                        spy("flash_fwd", fa_mod.flash_attention_fwd, interpret=True))
    monkeypatch.setattr(attn_mod, "chunked_causal_attention",
                        spy("chunked", attn_mod.chunked_causal_attention))
    return called


@pytest.mark.parametrize("window,path", [(None, "splash"), ("traced", "flash_fwd")])
def test_ops_dispatch_on_tpu(monkeypatch, window, path):
    """On a TPU, where no gradient is taken (prefill), a static window goes
    to the splash kernel, a traced one (gemma2's alternation) to the
    forward-only kernel."""
    from repro.kernels import ops
    called = _spy_kernels(monkeypatch)
    q, k, v = _qkv(1, 256, 4, 2, 64, jnp.float32)

    def f(w):
        return ops.flash_attention(q, k, v, window=w, scale=0.125, train=False)

    if window is None:
        out = f(None)
    else:
        out = jax.jit(f)(jnp.asarray(0, jnp.int32))
    assert called == [path]
    want = ref.flash_attention(q, k, v, window=None, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("window,path", [(None, "splash"), ("traced", "chunked")])
def test_ops_training_dispatch_on_tpu(monkeypatch, window, path):
    """On a TPU, where a gradient is taken, a static window goes to the
    splash kernel; a traced one never reaches the forward-only kernel,
    which has no backward pass, and takes the jnp path. The gradients of
    q, k and v match the dense oracle's."""
    from repro.kernels import ops
    called = _spy_kernels(monkeypatch)
    q, k, v = _qkv(1, 256, 4, 2, 64, jnp.float32)
    w = jnp.asarray(RNG.normal(0, 1, (1, 256, 4, 64)), jnp.float32)

    def loss(q, k, v, win, fn):
        return jnp.sum(fn(q, k, v, window=win, scale=0.125) * w)

    def grads(fn):
        if window is None:
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v, None, fn)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)), static_argnums=4)(
            q, k, v, jnp.asarray(0, jnp.int32), fn)

    got = grads(functools.partial(ops.flash_attention, train=True))
    assert called == [path]
    want = grads(ref.flash_attention)
    for g, want_g in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_g),
                                   atol=1e-2, rtol=1e-2)
