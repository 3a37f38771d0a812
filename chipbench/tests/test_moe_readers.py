"""The MoE cell's readers: the driver's map from named scopes to compiled
op names, and the per-layer metrics it feeds, on hand-made inputs."""
from types import SimpleNamespace

import pytest

from chipbench import harness, tracefold
from chipbench.drivers import train_moe

HLO = """
  %fusion.7 = bf16[8,2048]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/moe.dispatch/gather"}
  %ragged-dot-metadata = (s32[9]{0}) custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.3 = bf16[256,64]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.9 = bf16[256,64]{1,0} fusion(%x), metadata={op_name="jit(step)/transpose(jvp(moe.experts))/mul"}
  %fusion.11 = f32[8,16,512]{2,1,0} fusion(%y), metadata={op_name="jit(step)/checkpoint/rematted_computation/mla.attend/exp"}
  %add.2 = f32[] add(%u, %v)
"""


def test_scope_ops_reads_metadata_and_the_compilers_grouped_products():
    ops = train_moe.scope_ops(HLO)
    assert ops["moe.dispatch"] == {"fusion.7"}
    assert ops["moe.experts"] == {"ragged-dot-metadata", "ragged-dot-none.3", "fusion.9"}
    assert ops["mla.attend"] == {"fusion.11"}
    assert ops["moe.route"] == ops["moe.combine"] == set()


def _run(scope_ops):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(bench, "deepseek-v2-lite.train_4k_ep8")
    dev = tracefold.DeviceTrace(ops=[
        (0.0, 4e6, "%ragged-dot-none.3 = bf16[256,64] custom-call(%a)"),
        (4e6, 5e6, "%fusion.9 = bf16[256,64] fusion(%x)"),
        (5e6, 8e6, "%fusion.7 = bf16[8,2048] fusion(%p)"),
        (8e6, 9e6, "%fusion.11 = f32[8,16,512] fusion(%y)")])
    trace = tracefold.Trace(devices={"/device:TPU:0": dev},
                            spans=[(0.0, 10e6, "window")])
    fold = tracefold.fold(trace)
    return SimpleNamespace(cell=cell, fold=fold, devices=[SimpleNamespace(
        device_kind="TPU v5 lite")], facts={"steps": 2, "scope_ops": scope_ops,
                                             "tokens_per_s": 1.0})


@pytest.mark.parametrize("metric, want_ms", [
    ("moe_experts.device_ms", 2.5), ("moe_dispatch.device_ms", 1.5)])
def test_scope_readers(metric, want_ms):
    ops = {k: sorted(v) for k, v in train_moe.scope_ops(HLO).items()}
    assert harness.read_metric(_run(ops), metric) == pytest.approx(want_ms)
    # a run that recorded no scopes (an untraced run, or the parent) reads nothing
    assert harness.read_metric(_run({}), metric) is None


def test_expert_roofline_is_the_count_over_the_scope_time():
    from chipbench import work, work_moe
    ops = {k: sorted(v) for k, v in train_moe.scope_ops(HLO).items()}
    run = _run(ops)
    least = 2 * work_moe.expert_flops_per_step(run.cell.config, run.cell.mix) / \
        work.peaks("TPU v5 lite")["bf16_flops_per_s"]
    got = harness.read_metric(run, "moe_experts_roofline")
    assert got == pytest.approx(100.0 * least / 5e-3)
