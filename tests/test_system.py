"""End-to-end behaviour: the fault-tolerant training loop (paper Fig. 1/3).

RUN -> fault -> DETECT (real C4D pipeline) -> ISOLATE (backup swap) ->
RESTORE (checkpoint) -> RUN, with a deterministic data stream so the
restarted run is bitwise-reproducible.
"""
import jax
import numpy as np

from repro.common.config import ShapeSpec
from repro.configs import get_smoke_config
from repro.core.faults import Fault
from repro.train.trainer import FaultInjector, Trainer


def small_run():
    return get_smoke_config("smollm-135m")


def test_trainer_runs_and_checkpoints(tmp_path):
    run = small_run()
    shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")
    tr = Trainer(run, shape, workdir=str(tmp_path), checkpoint_async=False)
    rep = tr.train(12)
    assert rep.steps_run == 12
    assert tr.ckpt.save_count >= 2           # every 10 steps + step-0
    assert all(np.isfinite(l) for l in rep.losses)


def test_trainer_fault_detect_isolate_restore(tmp_path):
    run = small_run()
    shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")
    tr = Trainer(run, shape, workdir=str(tmp_path), sim_nodes=4,
                 checkpoint_async=False)
    inj = FaultInjector({7: Fault("crash", rank=9)})
    rep = tr.train(14, injector=inj)
    assert rep.restarts == 1
    det = rep.detections[0]
    assert det["fault"] == "crash"
    assert det["isolated"], "backup swap must have happened"
    out_node, in_node = det["isolated"][0]
    assert out_node == 9 // 8                # the faulty rank's node
    assert det["restored_step"] <= 7
    assert rep.steps_run == 14 - det["restored_step"] + 7

    # the isolated node left the active set; a backup joined
    assert out_node not in tr.cluster.active_nodes
    assert in_node in tr.cluster.active_nodes


def test_restarted_run_is_deterministic(tmp_path):
    """Final params after a mid-run fault + restore must equal a fault-free
    run (checkpoint restore + seed-addressable data => exact replay)."""
    run = small_run()
    shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")

    tr1 = Trainer(run, shape, workdir=str(tmp_path / "a"), checkpoint_async=False)
    tr1.train(12)

    tr2 = Trainer(run, shape, workdir=str(tmp_path / "b"), checkpoint_async=False)
    inj = FaultInjector({6: Fault("slow_src", rank=3)})
    rep2 = tr2.train(12, injector=inj)
    assert rep2.restarts == 1

    for a, b in zip(jax.tree.leaves(tr1.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restart_deterministic_on_sharded_mesh():
    """Table 3's restart story must hold beyond the trivial 1x1 layout: on a
    2x2 data x model mesh (FSDP+TP actually partitioning params and batch),
    a faulted + restored run must match the fault-free run bitwise."""
    from _subproc import run_child
    out = run_child("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import tempfile
        import jax, numpy as np
        from repro.common import jax_compat as jc
        from repro.common.config import ShapeSpec
        from repro.configs import get_smoke_config
        from repro.core.faults import Fault
        from repro.train.trainer import FaultInjector, Trainer

        run = get_smoke_config("smollm-135m")
        shape = ShapeSpec("t", run.train.seq_len, run.train.global_batch, "train")

        def mesh22():
            return jc.make_mesh((2, 2), ("data", "model"),
                                axis_types=(jc.AxisType.Auto,) * 2)

        with tempfile.TemporaryDirectory() as tmp:
            tr1 = Trainer(run, shape, workdir=os.path.join(tmp, "a"),
                          mesh=mesh22(), checkpoint_async=False)
            # FSDP shards the optimizer moments from step 0 on
            moments = jax.tree_util.tree_leaves(tr1.opt_state["m"])
            assert any(not m.sharding.is_fully_replicated for m in moments)
            tr1.train(12)
            tr2 = Trainer(run, shape, workdir=os.path.join(tmp, "b"),
                          mesh=mesh22(), checkpoint_async=False)
            rep2 = tr2.train(12, injector=FaultInjector({6: Fault("crash", rank=5)}))
        assert rep2.restarts == 1, rep2
        # params must come back partitioned, not silently replicated
        leaves = jax.tree_util.tree_leaves(tr2.params)
        assert any(len(l.sharding.device_set) > 1 for l in leaves)
        for a, b in zip(jax.tree_util.tree_leaves(tr1.params), leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("SHARDED_RESTART_OK")
    """)
    assert "SHARDED_RESTART_OK" in out


def test_straggler_detected_by_step_monitor():
    import time

    from repro.train.hooks import StepMonitor

    mon = StepMonitor(warmup_steps=3, mad_threshold=6.0)
    rng = np.random.default_rng(0)
    for s in range(10):
        mon.start()
        time.sleep(0.004 + 0.0002 * rng.random())
        st = mon.stop(s)
    mon.start()
    time.sleep(0.08)                      # 20x slower step
    st = mon.stop(10)
    assert st.anomalous and st.z > 6.0


def test_downtime_table3_reproduction():
    from repro.core.downtime import table3
    res = table3(seed=1, n_nodes=128)
    base = res["jun_2023_baseline"].fractions()["total"]
    c4d = res["dec_2023_c4d"].fractions()["total"]
    assert 0.22 < base < 0.45              # paper: 31.19%
    assert c4d < 0.02                      # paper: 1.16%
    assert base / c4d > 15                 # paper: ~27x
    rep = res["dec_2023_c4d"]
    assert rep.localized / max(rep.n_errors, 1) > 0.5


def test_cluster_backup_pool_exhaustion():
    from repro.core.cluster import SimCluster
    c = SimCluster(n_active=4, n_backup=2)
    assert c.isolate_and_replace(0) is not None
    assert c.isolate_and_replace(1) is not None
    assert c.isolate_and_replace(2) is None   # pool drained
    assert len(c.active_nodes) == 3
