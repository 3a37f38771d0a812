"""Share of the roofline reached by the held experts' grouped products, in
%.

The count (``work_moe.expert_flops_per_step``): per microbatch and MoE
layer, the configuration's B budget rows through the gate, up and down
products, 2 x B x 3 x d x f FLOP, four times (forward, recomputation under
full remat, and the backward pass's two products per weight).  The least
time is that count over the bf16 peak (the products are compute-bound:
each weight is read once for B rows); the share is that least time over
the device time of the ops under ``moe.experts``."""

from chipbench import work, work_moe


def read(run):
    seconds = work_moe.scope_device_s(run, ["moe.experts"])
    steps = run.facts.get("steps")
    if seconds is None or not steps:
        return None
    cfg, mix = run.cell.config, run.cell.mix
    peak = work.peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    least = steps * work_moe.expert_flops_per_step(cfg, mix) / peak
    return 100.0 * least / seconds
