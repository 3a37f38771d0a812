import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input shape x mesh) cell
lowers, SPMD-partitions, and compiles on the production meshes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k [--multi-pod] [--roofline] [--out experiments/dryrun]

Per cell this records compiled.memory_analysis() (proves the per-device
footprint), cost_analysis() (FLOPs/bytes), the collective mix parsed from
the HLO, and — with --roofline — the trip-count-corrected roofline terms
(see launch/roofline.py).

NOTE: the two os.environ lines above MUST stay the first statements —
jax locks the device count at first init.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import jax_compat as jc
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import RunConfig, SHAPES, ShapeSpec, shape_applicable
from repro.configs import ARCHS, get_config
from repro.launch import mesh as meshmod
from repro.launch import roofline as rl
from repro.models.model import count_params_analytic, input_specs
from repro.models.transformer import LM
from repro.optim import adamw
from repro.parallel import sharding as shd
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step


# ---------------------------------------------------------------------------
# Reduced-depth configs for per-unit cost extraction
# ---------------------------------------------------------------------------

def with_units(run: RunConfig, k: int) -> RunConfig:
    cfg = run.model
    if cfg.cross_attn_every:
        n = k * cfg.cross_attn_every
    elif cfg.shared_attn_every and cfg.ssm is not None:
        rem = cfg.n_layers % cfg.shared_attn_every
        n = k * cfg.shared_attn_every + rem
    elif cfg.block_pattern:
        n = k * len(cfg.block_pattern)
    elif cfg.local_global_alternating:
        n = 2 * k
    elif cfg.moe is not None and cfg.first_k_dense:
        n = cfg.first_k_dense + k
    else:
        n = k
    return run.replace(model=dataclasses.replace(cfg, n_layers=n))


def full_units(run: RunConfig) -> int:
    cfg = run.model
    if cfg.cross_attn_every:
        return cfg.n_layers // cfg.cross_attn_every
    if cfg.shared_attn_every and cfg.ssm is not None:
        return cfg.n_layers // cfg.shared_attn_every
    if cfg.block_pattern:
        return cfg.n_layers // len(cfg.block_pattern)
    if cfg.local_global_alternating:
        return cfg.n_layers // 2
    if cfg.moe is not None and cfg.first_k_dense:
        return cfg.n_layers - cfg.first_k_dense
    return cfg.n_layers


# ---------------------------------------------------------------------------
# Lowering one cell
# ---------------------------------------------------------------------------

def lower_cell(run: RunConfig, shape: ShapeSpec, mesh, *,
               unroll: bool = False, donate: bool = True):
    """Lower + compile one (config x shape) on ``mesh``. Returns compiled."""
    sp = run.parallel.attn_activation_sharding
    if sp == "auto":
        sp = "batch" if (run.model.n_kv_heads % 16 != 0
                         and run.model.mla is None) else "off"
    sp_attn = "" if sp == "off" else sp
    model = LM(run.model, param_dtype=jnp.dtype(run.parallel.param_dtype),
               remat=run.parallel.remat, use_kernel=False, unroll=unroll,
               sp_attn=sp_attn)
    ins = input_specs(run.model, shape)
    az = run.parallel.attn_zero_sharding
    tp = 16
    attn_zero = (az == "on") or (az == "auto" and run.model.n_heads % tp != 0
                                 and run.model.mla is None)
    moe_zero = run.parallel.moe_weight_sharding == "zero"
    with jc.set_mesh(mesh):
        abstract_params = jax.eval_shape(model.init, jax.random.key(0))
        pspecs = shd.param_specs(abstract_params, mesh, attn_zero=attn_zero,
                                 moe_zero=moe_zero)
        pshard = shd.to_shardings(pspecs, mesh)
        bshard = shd.to_shardings(shd.batch_specs(ins, mesh), mesh)

        if shape.kind == "train":
            # override batch for the shape grid
            tr = dataclasses.replace(run.train, seq_len=shape.seq_len,
                                     global_batch=shape.global_batch)
            run2 = run.replace(train=tr)
            opt_cfg = adamw.OptimizerConfig(kind=run.parallel.optimizer_state)
            step = make_train_step(model, run2, opt_cfg, mesh)
            abstract_opt = jax.eval_shape(
                lambda p: adamw.init_state(opt_cfg, p), abstract_params)
            oshard = shd.to_shardings(
                shd.opt_state_specs(abstract_opt, abstract_params, pspecs),
                mesh)
            jitted = jax.jit(step,
                             in_shardings=(pshard, oshard, bshard),
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(abstract_params, abstract_opt, ins)
        elif shape.kind == "prefill":
            pre = make_prefill_step(model)
            cache_dt = jnp.dtype(run.parallel.kv_cache_dtype)
            abstract_cache = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         dtype=cache_dt))
            cshard = shd.to_shardings(shd.cache_specs(abstract_cache, mesh), mesh)
            jitted = jax.jit(pre, in_shardings=(pshard, bshard, cshard),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(abstract_params, ins, abstract_cache)
        else:  # decode
            dec = make_decode_step(model)
            cache_dt = jnp.dtype(run.parallel.kv_cache_dtype)
            abstract_cache = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         dtype=cache_dt))
            cshard = shd.to_shardings(shd.cache_specs(abstract_cache, mesh), mesh)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(dec, in_shardings=(pshard, bshard, cshard, None),
                             donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(abstract_params, ins, abstract_cache, pos)
        compiled = lowered.compile()
    return compiled


# ---------------------------------------------------------------------------
# Cell driver
# ---------------------------------------------------------------------------

def cpu_float_normalization_bytes(hlo_text: str) -> int:
    """XLA:CPU's FloatNormalization pass upcasts bf16 loop-carried residual
    stacks to f32 (CPU has no native bf16); on the TPU target those stacks
    stay bf16.  Estimate the inflation: every f32 buffer whose dims exactly
    match a bf16 buffer (and is 2x its size) is counted as an artifact.
    Verified against a minimal scan+checkpoint repro (see EXPERIMENTS.md)."""
    import re as _re
    seen_bf16 = set()
    f32 = {}
    for m in _re.finditer(r"(bf16|f32)\[([0-9,]+)\]", hlo_text):
        dt, dims = m.group(1), m.group(2)
        if dt == "bf16":
            seen_bf16.add(dims)
        else:
            f32[dims] = True
    total = 0
    for dims in f32:
        if dims in seen_bf16 and dims:
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n * 4 >= 1 << 28:   # only count >=256 MiB artifacts
                total += n * 4
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             do_roofline: bool, out_dir: str) -> Dict:
    run = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = meshmod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi_pod_2x16x16" if multi_pod else "single_pod_16x16"
    chips = int(np.prod(list(mesh.shape.values())))
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "chips": chips, "status": "unknown"}
    if not shape_applicable(run.model, shape):
        rec["status"] = "skipped_by_design"
        rec["reason"] = "long_500k requires sub-quadratic attention / compressed cache"
        return _write(rec, out_dir)
    t0 = time.time()
    try:
        compiled = lower_cell(run, shape, mesh)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        coll = rl.parse_collectives(hlo_text)
        peak = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                   - ma.alias_size_in_bytes)
        cpu_artifact = cpu_float_normalization_bytes(hlo_text)
        # floor at the live argument set: params/opt/cache must stay resident
        tpu_peak = max(peak - cpu_artifact,
                       int(ma.argument_size_in_bytes - ma.alias_size_in_bytes),
                       int(ma.argument_size_in_bytes) // 2)
        rec.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_estimate_bytes": peak,
                "cpu_float_norm_artifact_bytes": int(cpu_artifact),
                "tpu_corrected_peak_bytes": int(tpu_peak),
                "hbm_limit_bytes": int(meshmod.HBM_BYTES),
                "fits": bool(tpu_peak < meshmod.HBM_BYTES),
            },
            "cost_analysis": {"flops_per_device_scanbody_once": float(ca.get("flops", 0.0)),
                              "bytes_per_device_scanbody_once": float(ca.get("bytes accessed", 0.0))},
            "collectives_scanbody_once": {"counts": coll.counts,
                                          "wire_bytes_per_device": coll.wire_bytes},
        })
        del compiled
        if do_roofline:
            rec["roofline"] = roofline_cell(run, shape, mesh, mesh_name, chips, arch)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return _write(rec, out_dir)


def roofline_cell(run: RunConfig, shape: ShapeSpec, mesh, mesh_name: str,
                  chips: int, arch: str) -> Dict:
    """Trip-count-corrected roofline from unrolled 1-unit / 2-unit diffs.

    ALL loops are unrolled for these lowerings (layer scan via model.unroll;
    microbatch/CE/attention/SSD chunk scans via REPRO_UNROLL_SCANS) because
    cost_analysis counts any while-loop body once."""
    run = run.replace(parallel=dataclasses.replace(run.parallel, microbatches=1))
    os.environ["REPRO_UNROLL_SCANS"] = "1"
    try:
        c1 = rl.CostTerms.of(lower_cell(with_units(run, 1), shape, mesh, unroll=True))
        c2 = rl.CostTerms.of(lower_cell(with_units(run, 2), shape, mesh, unroll=True))
    finally:
        os.environ.pop("REPRO_UNROLL_SCANS", None)
    per_unit = c2.diff(c1)
    units = full_units(run)
    total = c1.extrapolate(per_unit, units - 1)
    n_active = count_params_analytic(run.model, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = rl.model_flops_estimate(n_active, tokens, shape.kind)
    roof = rl.roofline_terms(arch, shape.name, mesh_name, chips, total, mf, 0.0)
    # TPU-expected memory term (fusion-aware structural estimate); the HLO
    # "bytes accessed" term is an unfused upper bound on the CPU lowering
    struct_bytes = rl.structural_hbm_bytes(run, shape, chips)
    t_mem_tpu = struct_bytes / meshmod.HBM_BW
    terms_tpu = {"compute": roof.t_comp, "memory": t_mem_tpu,
                 "collective": roof.t_coll}
    dominant_tpu = max(terms_tpu, key=terms_tpu.get)
    ideal = mf / (chips * meshmod.PEAK_FLOPS_BF16)
    frac_tpu = ideal / max(max(terms_tpu.values()), 1e-30)
    return {
        "t_comp_s": roof.t_comp, "t_mem_hlo_s": roof.t_mem,
        "t_mem_tpu_s": t_mem_tpu, "t_coll_s": roof.t_coll,
        "dominant_hlo": roof.dominant, "dominant": dominant_tpu,
        "model_flops": mf,
        "hlo_flops_global": roof.hlo_flops,
        "useful_flops_ratio": roof.useful_flops_ratio,
        "roofline_fraction_hlo": roof.roofline_fraction,
        "roofline_fraction": frac_tpu,
        "collective_counts": total.coll.counts,
        "collective_wire_bytes_per_device": total.coll.wire_bytes,
        "units_extrapolated": units,
    }


def _write(rec: Dict, out_dir: str) -> Dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{rec['mesh']}__{rec['arch']}__{rec['shape']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    status = rec["status"]
    extra = ""
    if status == "ok":
        mem = rec["memory"]["tpu_corrected_peak_bytes"] / 2**30
        raw = rec["memory"]["peak_estimate_bytes"] / 2**30
        extra = (f" mem/dev={mem:.2f}GiB (cpu-raw {raw:.2f})"
                 f" fits={rec['memory']['fits']}")
        if "roofline" in rec:
            r = rec["roofline"]
            extra += (f" comp={r['t_comp_s']:.3g}s mem={r['t_mem_tpu_s']:.3g}s "
                      f"coll={r['t_coll_s']:.3g}s dom={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}")
    print(f"[{status}] {rec['mesh']} {rec['arch']} {rec['shape']}{extra}", flush=True)
    return rec


def refresh_roofline(arch: str, shape_name: str, out_dir: str) -> Dict:
    """Recompute only the roofline section of an existing single-pod record."""
    run = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(run.model, shape):
        return {"status": "skipped_by_design", "arch": arch, "shape": shape_name}
    mesh = meshmod.make_production_mesh(multi_pod=False)
    chips = int(np.prod(list(mesh.shape.values())))
    path = os.path.join(out_dir, f"single_pod_16x16__{arch}__{shape_name}.json")
    rec = json.load(open(path)) if os.path.exists(path) else {
        "arch": arch, "shape": shape_name, "mesh": "single_pod_16x16",
        "chips": chips, "status": "ok"}
    try:
        rec["roofline"] = roofline_cell(run, shape, mesh, "single_pod_16x16",
                                        chips, arch)
    except Exception as e:
        rec["roofline_error"] = f"{type(e).__name__}: {e}"
    return _write(rec, out_dir)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--roofline-only", action="store_true",
                    help="recompute only roofline terms into existing records")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    enable_compile_cache()

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    if args.roofline_only:
        for arch in archs:
            for shape in shapes:
                refresh_roofline(arch, shape, args.out)
        return
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.roofline and not mp, args.out)
                if rec["status"] == "error":
                    failures += 1
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
