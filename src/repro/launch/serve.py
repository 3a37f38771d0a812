"""Serving entry point: prefill + batched decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
        --prompt-len 64 --decode-steps 16 --batch 2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import jax_compat as jc
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import ShapeSpec
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model, synthetic_batch
from repro.train.steps import make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    run = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(args.data, args.model)
    model = build_model(run, use_kernel=False)
    max_len = args.prompt_len + args.decode_steps

    with jc.set_mesh(mesh):
        params = jax.jit(model.init)(jax.random.key(0))
        shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_batch(run.model, shape, seed=1).items()}
        cache = model.init_cache(args.batch, max_len,
                                 dtype=jnp.dtype(run.parallel.param_dtype))
        prefill = jax.jit(make_prefill_step(model))
        decode = jax.jit(make_decode_step(model))

        t0 = time.perf_counter()
        logits, cache = prefill(params, batch, cache)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out_tokens = [tokens]
        t0 = time.perf_counter()
        for i in range(args.decode_steps):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            step_batch = dict(batch)
            if "tokens" in batch:
                step_batch["tokens"] = tokens[:, None]
            else:  # audio: feed the embedding of the sampled token (stub frontend)
                step_batch["embeddings"] = jnp.zeros(
                    (args.batch, 1, run.model.d_model),
                    jnp.dtype(run.parallel.param_dtype))
            logits, cache = decode(params, step_batch, cache, pos)
            tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            out_tokens.append(tokens)
        jax.block_until_ready(tokens)
        t_decode = time.perf_counter() - t0

    toks = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(json.dumps({
        "arch": run.model.name,
        "prefill_s": round(t_prefill, 4),
        "decode_s": round(t_decode, 4),
        "decode_tok_per_s": round(args.batch * args.decode_steps / max(t_decode, 1e-9), 1),
        "sampled_tokens_head": toks[:, :8].tolist(),
    }, indent=1))


if __name__ == "__main__":
    main()
