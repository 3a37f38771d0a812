"""Model FLOP utilisation of an MoE training window, in %: the active
model FLOP per token (``work_moe.train_flops_per_token``: 6 x the weights
a token is multiplied by on this chip, the routed experts at the chip's
budget, plus attention's products; recompute not counted) times the
window's tokens per second, over the chips' bf16 peak."""

from chipbench import work, work_moe


def read(run):
    tokens_per_s = run.facts.get("tokens_per_s")
    if not tokens_per_s:
        return None
    cfg, mix = run.cell.config, run.cell.mix
    flops = work_moe.train_flops_per_token(cfg, mix["seq_len"]) * tokens_per_s
    peak = work.peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (len(run.devices) * peak)
