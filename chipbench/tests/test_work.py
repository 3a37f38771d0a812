"""The operation and byte counts of ``chipbench/work.py`` and the peak
table, against numbers worked out by hand."""
import pytest

from chipbench import work
from chipbench.harness import HERE, load_json

SMOLLM = load_json(HERE / "configs" / "smollm-135m.json")


def test_smollm_parameter_count():
    # embedding 49,152 x 576; per layer q/o 576 x 576, k/v 576 x 192,
    # gate/up/down 3 x 576 x 1,536, two norms of 576; final norm 576
    embed = 49_152 * 576
    layer = 2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1_536 + 2 * 576
    assert work.dense_param_count(SMOLLM) == embed + 30 * layer + 576 == 134_515_008


def test_smollm_step_flops():
    # 6N + 12 L d S per token, 16 x 4,096 tokens a step: 1.085e14
    per_token = 6 * 134_515_008 + 12 * 30 * 576 * 4_096
    assert work.train_flops_per_token(SMOLLM, 4_096) == per_token
    assert work.train_flops_per_token(SMOLLM, 4_096) * 65_536 == pytest.approx(
        1.0853e14, rel=1e-3)


def test_detect_window_bytes():
    # 31,250 transports, 30,720 pairs, 102,400 heartbeats, 10,240 ranks:
    # fused: 2T + 3G + 3H in, 2G + 5N out = 574,500 elements
    # fold:  8G in, 3G + 10N out = 440,320 elements; 8 bytes each
    hang = work.WindowSizes(31_250, 30_720, 102_400, 10_240, fold=False)
    slow = work.WindowSizes(31_250, 30_720, 102_400, 10_240, fold=True)
    assert work.detect_window_bytes(hang) == 8 * 574_500
    assert work.detect_window_bytes(slow) == 8 * (574_500 + 440_320)


def test_peaks_table():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
