"""Model FLOP utilisation of the training window, in %: model FLOP per
token (``work.train_flops_per_token``: 6N + 12 L d S, recompute not
counted) times the window's tokens per second, over the chips' bf16 peak."""

from chipbench import work


def read(run):
    cfg, mix = run.cell.config, run.cell.mix
    flops = work.train_flops_per_token(cfg, mix["seq_len"]) * run.facts["tokens_per_s"]
    peak = work.peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (len(run.devices) * peak)
