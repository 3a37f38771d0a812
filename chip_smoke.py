"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

With no option, three phases run in this one process, each through the
entry points a user calls:

  * kernels: the three Pallas kernels (flash attention, decode attention,
    RMSNorm), compiled for the chip, against the pure-jnp oracles of
    ``repro.kernels.ref`` at smollm-135m widths;
  * detection: two ``C4DMaster`` instances at the 10,240-rank ``fleet_day``
    size score the very same telemetry windows (fault-free, slow_src,
    slow_link, comm_hang, crash), one on the jax backend (the device
    path), one on the NumPy reference; verdicts and node actions must be
    identical;
  * training: the ``Trainer`` on smollm-135m at full width and depth with
    random weights from seed 0, global batch cut from 256 to 16 to fit one
    chip, 8 steps with a crash injected at step 4 — C4D verdict, node
    swap, re-jit, checkpoint restore and deterministic replay.

``--chips 4`` runs only the data-parallel path: the same Trainer on a
``data=4`` mesh for 3 steps, and the same 3 steps on one chip, whose
losses and gradient norms must agree.

Exits non-zero, without a result line, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object naming the
device, as JAX reports it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common import jax_compat as jc  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.common.config import ShapeSpec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.c4d.master import C4DMaster  # noqa: E402
from repro.core.faults import Fault, RingJobTelemetry  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention_fwd  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm_fwd  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.train.trainer import FaultInjector, Trainer  # noqa: E402

ARCH = "smollm-135m"
SEQ_LEN = 4096
#: the published train_4k cell is 4096 x 256; 16 sequences fit one chip
GLOBAL_BATCH = 16
FLEET_RANKS = 10_240          # scenarios/fleet.py: fleet_day
RANKS_PER_NODE = 8
#: bf16 keeps 8 significant bits (relative step 2**-8 ~ 3.9e-3); a kernel
#: output that accumulates in float32 may differ from the float32 oracle by
#: a few such steps after rounding to bf16.
KERNEL_TOL = 2e-2
#: sharded and one-chip steps sum the same bf16 products in another order:
#: losses and gradient norms then agree to a few bf16 steps.
SHARDED_RTOL = 1e-2
#: the replay after a restore runs the same program on the same inputs.
REPLAY_RTOL = 1e-6


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _rel_err(out, want) -> float:
    """max |out - want| / (|want| + 1): at most ``tol`` exactly when
    ``assert_allclose(out, want, atol=tol, rtol=tol)`` passes."""
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(out - want) / (np.abs(want) + 1.0)))


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def kernels_phase(batch: int, seq: int, n_heads: int, n_kv: int,
                  head_dim: int, d_model: int) -> None:
    """Each Pallas kernel once, compiled for the chip, against its oracle."""
    log(f"[kernels] B={batch} S={seq} H={n_heads} KV={n_kv} D={head_dim} "
        f"d_model={d_model} bf16, tolerance {KERNEL_TOL}")
    keys = jax.random.split(jax.random.key(0), 6)
    bf16 = jnp.bfloat16
    q = jax.random.normal(keys[0], (batch, seq, n_heads, head_dim), bf16)
    k = jax.random.normal(keys[1], (batch, seq, n_kv, head_dim), bf16)
    v = jax.random.normal(keys[2], (batch, seq, n_kv, head_dim), bf16)
    scale = head_dim ** -0.5

    flash = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, window=None, scale=scale))
    out, first = _timed(flash, q, k, v)
    _, again = _timed(flash, q, k, v)
    want = jax.jit(lambda q, k, v: ref.flash_attention(
        q, k, v, window=None, scale=scale))(q, k, v)
    err = _rel_err(out, want)
    log(f"[kernels] flash_attention_fwd: first call {first:.3f} s "
        f"(compile included), second {again:.4f} s, max rel err {err:.3e}")
    check(bool(jnp.all(jnp.isfinite(out))), "flash attention output finite")
    check(err <= KERNEL_TOL, "flash attention matches ref.flash_attention")
    del out, want

    qd = q[:, :1]
    pos = jnp.asarray(seq - 100, jnp.int32)
    decode = jax.jit(lambda q, k, v, p: decode_attention_fwd(
        q, k, v, p, window=None, scale=scale))
    out, first = _timed(decode, qd, k, v, pos)
    want = ref.decode_attention(qd, k, v, pos, window=None, scale=scale)
    err = _rel_err(out, want)
    log(f"[kernels] decode_attention_fwd: first call {first:.3f} s, "
        f"max rel err {err:.3e}")
    check(bool(jnp.all(jnp.isfinite(out))), "decode attention output finite")
    check(err <= KERNEL_TOL, "decode attention matches ref.decode_attention")
    del q, k, v, qd

    x = jax.random.normal(keys[3], (batch, seq, d_model), bf16)
    sc = 0.1 * jax.random.normal(keys[4], (d_model,), bf16)
    out, first = _timed(jax.jit(rmsnorm_fwd), x, sc)
    err = _rel_err(out, ref.rmsnorm(x, sc))
    log(f"[kernels] rmsnorm_fwd: first call {first:.3f} s, "
        f"max rel err {err:.3e}")
    check(err <= KERNEL_TOL, "rmsnorm matches ref.rmsnorm")


# ---------------------------------------------------------------------------
# detection
# ---------------------------------------------------------------------------

def _verdict_key(v):
    return (v.syndrome, v.rank, v.link)


def _action_key(a):
    return (a.node_id, a.action, [_verdict_key(v) for v in a.verdicts])


def detection_windows(n_ranks: int):
    """(label, fault or None, node the window's actions must include or
    None).  Slow syndromes act on their second consecutive window
    (``C4DMaster.confirm_windows``), hangs and crashes at once."""
    slow = Fault("slow_src", rank=n_ranks // 8 + 2)
    link = Fault("slow_link", link=(n_ranks // 2, n_ranks // 2 + 1))
    hang = Fault("comm_hang", rank=n_ranks // 3)
    crash = Fault("crash", rank=n_ranks - 5)

    def node(rank):
        return rank // RANKS_PER_NODE

    return [
        ("fault-free", None, None),
        ("slow_src", slow, None),
        ("slow_src", slow, node(slow.rank)),
        ("slow_link", link, None),
        ("slow_link", link, node(link.link[0])),
        ("comm_hang", hang, node(hang.rank)),
        ("crash", crash, node(crash.rank)),
    ]


def detection_phase(n_ranks: int) -> None:
    """The jax-backend master against the NumPy master, window for window."""
    log(f"[detection] C4DMaster at {n_ranks} ranks, "
        f"{RANKS_PER_NODE} ranks per node, jax backend vs numpy reference")
    telemetry = RingJobTelemetry(n_ranks=n_ranks)
    dev = C4DMaster(n_ranks=n_ranks, ranks_per_node=RANKS_PER_NODE,
                    backend="jax")
    host = C4DMaster(n_ranks=n_ranks, ranks_per_node=RANKS_PER_NODE,
                     backend="numpy")
    worst = 0.0
    n_verdicts = 0
    for wid, (label, fault, node) in enumerate(detection_windows(n_ranks)):
        # generated once: synthesis advances the telemetry's RNG
        window = telemetry.window_arrays(
            window_id=wid, faults=[fault] if fault else [])
        t0 = time.perf_counter()
        dev_actions = dev.ingest(window)
        t_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        host_actions = host.ingest(window)
        t_host = time.perf_counter() - t0
        dev_v = dev.offline_log[-1][1]
        host_v = host.offline_log[-1][1]
        log(f"[detection] window {wid} ({label}, "
            f"{window.tr_src.size} transports): jax {t_dev:.3f} s, "
            f"numpy {t_host:.3f} s, {len(host_v)} verdicts, "
            f"actions on nodes {[a.node_id for a in host_actions]}")
        check([_verdict_key(v) for v in dev_v]
              == [_verdict_key(v) for v in host_v],
              f"window {wid}: jax verdicts equal numpy verdicts")
        check([_action_key(a) for a in dev_actions]
              == [_action_key(a) for a in host_actions],
              f"window {wid}: jax node actions equal numpy node actions")
        for a, b in zip(dev_v, host_v):
            worst = max(worst, abs(a.score - b.score)
                        / max(abs(b.score), 1e-300))
        n_verdicts += len(host_v)
        if node is not None:
            check(node in [a.node_id for a in host_actions],
                  f"window {wid}: the faulty node {node} is acted on")
    log(f"[detection] {n_verdicts} verdicts; largest relative score "
        f"difference jax vs numpy: {worst:.3e}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _trainer(run, shape, workdir, mesh=None):
    t0 = time.perf_counter()
    trainer = Trainer(run, shape, workdir=workdir, mesh=mesh)
    log(f"[train] Trainer built on mesh {dict(trainer.mesh.shape)} over "
        f"{trainer.mesh.devices.size} device(s) in "
        f"{time.perf_counter() - t0:.2f} s (parameter init compiled)")
    return trainer


def training_phase(run, shape, n_steps: int, crash_at: int) -> None:
    """RUN -> DETECT -> ISOLATE -> RESTORE on the chip, with replay."""
    log(f"[train] {run.model.name}: {run.model.n_layers} layers, d_model "
        f"{run.model.d_model}, vocab {run.model.vocab_size}, seq "
        f"{shape.seq_len}, global batch {shape.global_batch}, "
        f"{run.parallel.microbatches} microbatches, remat "
        f"{run.parallel.remat}; {n_steps} steps, crash at step {crash_at}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as workdir:
        trainer = _trainer(run, shape, workdir)
        injector = FaultInjector({crash_at: Fault("crash", rank=3)})
        t0 = time.perf_counter()
        report = trainer.train(n_steps, injector=injector)
        wall = time.perf_counter() - t0
        trainer.ckpt.close()
    steps = trainer.monitor.durations
    log(f"[train] {len(steps)} steps in {wall:.2f} s; per-step wall s "
        f"(first includes compile): {[round(d, 4) for d in steps]}")
    log(f"[train] losses: {report.losses}")
    log(f"[train] restarts: {report.restarts}; record: "
        f"{json.dumps(report.detections, default=str)}")
    losses = np.asarray(report.losses)
    check(bool(np.all(np.isfinite(losses))), "every loss is finite")
    # a random tied-embedding model starts near ln(vocab) plus about half
    # the logit variance: [10.0, 12.5] for smollm-135m's 49,152 tokens
    lo, hi = math.log(run.model.vocab_size) - 0.8, math.log(
        run.model.vocab_size) + 1.7
    check(lo <= losses[0] <= hi,
          f"first loss {losses[0]:.4f} lies in [{lo:.2f}, {hi:.2f}]")
    check(report.restarts == 1, "exactly one restart recorded")
    restored = report.detections[0]["restored_step"]
    before = losses[restored:crash_at]
    replay = losses[crash_at:crash_at + (crash_at - restored)]
    rel = float(np.max(np.abs(replay - before) / np.abs(before)))
    log(f"[train] replayed steps {restored}..{crash_at - 1}: largest "
        f"relative loss difference {rel:.3e}")
    check(rel <= REPLAY_RTOL,
          f"replayed losses equal pre-fault losses within {REPLAY_RTOL}")
    check(len(losses) == n_steps + crash_at - restored,
          "every step before the fault and after the restore ran")


def sharded_phase(run, shape, n_steps: int, n_chips: int) -> None:
    """The data-parallel Trainer against the same steps on one chip."""
    log(f"[sharded] {run.model.name} global batch {shape.global_batch}, "
        f"{n_steps} steps: data={n_chips} mesh vs one chip, rtol "
        f"{SHARDED_RTOL}")
    results = {}
    for label, mesh in (("one chip", None),
                        (f"data={n_chips}", make_local_mesh(data=n_chips))):
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as wd:
            trainer = _trainer(run, shape, wd, mesh=mesh)
            if mesh is not None:
                batch = {k: jnp.asarray(v)
                         for k, v in trainer.pipeline.batch(0).items()}
                with jc.set_mesh(trainer.mesh):
                    hlo = trainer._step_fn.lower(
                        trainer.params, trainer.opt_state,
                        batch).compile().as_text()
                found = [op for op in ("all-reduce", "reduce-scatter",
                                       "all-gather") if op in hlo]
                log(f"[sharded] collectives in the compiled step: {found}")
                check("all-reduce" in found or "reduce-scatter" in found,
                      "the sharded step reduces across devices")
            t0 = time.perf_counter()
            report = trainer.train(n_steps)
            trainer.ckpt.close()
        log(f"[sharded] {label}: {time.perf_counter() - t0:.2f} s, per-step "
            f"wall s {[round(d, 4) for d in trainer.monitor.durations]}")
        log(f"[sharded] {label}: losses {report.losses}, grad norms "
            f"{report.grad_norms}")
        results[label] = (np.asarray(report.losses),
                          np.asarray(report.grad_norms))
        del trainer, report
        gc.collect()
    (l1, g1), (ln, gn) = results.values()
    rel_loss = float(np.max(np.abs(ln - l1) / np.abs(l1)))
    rel_grad = float(np.max(np.abs(gn - g1) / np.abs(g1)))
    log(f"[sharded] largest relative difference: loss {rel_loss:.3e}, "
        f"grad norm {rel_grad:.3e}")
    check(bool(np.all(np.isfinite(ln))), "sharded losses finite")
    check(rel_loss <= SHARDED_RTOL, "sharded and one-chip losses agree")
    check(rel_grad <= SHARDED_RTOL, "sharded and one-chip grad norms agree")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind}, {len(devices)} visible; "
        f"jax {jax.__version__}; compile cache {cache}")

    run = get_config(ARCH)
    shape = ShapeSpec("train", SEQ_LEN, GLOBAL_BATCH, "train")
    log(f"reductions: {ARCH} global batch {run.train.global_batch} -> "
        f"{GLOBAL_BATCH} (published train_4k: {SEQ_LEN} x "
        f"{run.train.global_batch}); full width and depth; random weights "
        f"from seed {run.train.seed}; synthetic tokens")

    phases = ([("sharded", lambda: sharded_phase(run, shape, 3, args.chips))]
              if args.chips > 1 else
              [("kernels", lambda: kernels_phase(
                  2, SEQ_LEN, run.model.n_heads, run.model.n_kv_heads,
                  run.model.resolved_head_dim, run.model.d_model)),
               ("detection", lambda: detection_phase(FLEET_RANKS)),
               ("training", lambda: training_phase(run, shape, 8, 4))])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"phase {name}: {time.perf_counter() - t0:.2f} s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
