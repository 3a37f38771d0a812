"""Operations and bytes that the benchmarked work needs, from its shapes.

These counts are the numerators of every utilisation the benchmark
reports.  They are computed from the problem's own sizes (model widths,
tokens, transports, ranks), never from the padded shapes or the dtypes of
whatever program happens to run, so that a later program that pads less
or computes in another precision is held to the same count.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

#: the element width the detection byte count is stated in.  C4D's
#: specification scores in float64 (int64 keys and counters), so every
#: element the detection kernels must read or write counts 8 bytes.
DETECT_ELEMENT_BYTES = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that is
    not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# dense decoder training
# ---------------------------------------------------------------------------

def dense_param_count(cfg: dict) -> int:
    """Parameters of a Llama-style decoder (GQA attention, gated MLP,
    RMSNorm scales), the tied embedding counted once."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = 3 * d * ff
    per_layer = attn + mlp + 2 * d
    embed = vocab * d * (1 if cfg.get("tie_word_embeddings", True) else 2)
    return embed + layers * per_layer + d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOP per trained token: 6N for the matrix products of the
    forward and backward passes plus 12 * L * d * S for attention's score
    and value products.  Recomputation (remat) is not counted."""
    n = dense_param_count(cfg)
    return 6.0 * n + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq_len


# ---------------------------------------------------------------------------
# C4D detection kernels
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WindowSizes:
    """The problem sizes of one scored detection window."""
    transports: int      # transports after the agents' prefilter
    groups: int          # distinct (src, dst) pairs among them
    heartbeats: int
    ranks: int
    fold: bool           # the slow-path fold ran (no hang pre-empted it)


def detect_window_bytes(w: WindowSizes) -> int:
    """Bytes the detection kernels must move for one window.

    Pair medians and hang scoring read each transport's delay and wait
    value, each group's key, count and validity, and each heartbeat's
    rank, sequence and validity; they write two medians per group and five
    per-rank values (presence, last sequence, deficit, hang flag, is-source).
    The slow-path fold, which a hang pre-empts, reads each group's key,
    validity, two medians and four z normalisers, and writes two z-scores
    and a point flag per group and ten per-rank fold values."""
    elements = (2 * w.transports + 3 * w.groups + 3 * w.heartbeats
                + 2 * w.groups + 5 * w.ranks)
    if w.fold:
        elements += 8 * w.groups + 3 * w.groups + 10 * w.ranks
    return DETECT_ELEMENT_BYTES * elements
