"""Roofline analysis from compiled dry-run artifacts (TPU v5e targets).

Three terms per (arch x shape x mesh):

    t_comp = HLO_FLOPs        / (chips * 197e12)
    t_mem  = HLO_bytes        / (chips * 819e9)
    t_coll = collective_bytes / (chips * 50e9)

``cost_analysis()`` counts a ``scan`` body ONCE (verified empirically), so
totals are reconstructed from two *unrolled* reduced-depth lowerings:

    per_unit = cost(2 units) - cost(1 unit)
    total    = cost(1 unit)  + (n_units - 1) * per_unit

Collective bytes are parsed from ``compiled.as_text()``: every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute op's result
shape and replica group size, folded with ring wire factors:

    all-reduce      2 (N-1)/N * bytes     all-gather     (N-1)/N * bytes
    reduce-scatter  (N-1)/N * in_bytes    all-to-all     (N-1)/N * bytes
    collective-permute  bytes
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.launch import mesh as meshmod

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
               "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
               "u64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVE_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)
GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^{]*\}|\[[0-9]+,[0-9]+\]<=)")


def _shape_bytes(dtype: str, dims: str) -> float:
    b = DTYPE_BYTES.get(dtype, 4)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return float(b * n)


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[([0-9]+),([0-9]+)\]", line)
    if m:
        return int(m.group(2))
    return 2


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    raw_bytes: Dict[str, float] = field(default_factory=dict)
    wire_bytes: float = 0.0    # per-device bytes on the wire (ring factors)

    def add(self, kind: str, nbytes: float, group: int):
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.raw_bytes[kind] = self.raw_bytes.get(kind, 0.0) + nbytes
        n = max(group, 2)
        factor = {"all-reduce": 2 * (n - 1) / n,
                  "all-gather": (n - 1) / n,
                  "reduce-scatter": (n - 1) / n,
                  "all-to-all": (n - 1) / n,
                  "collective-permute": 1.0}[kind]
        self.wire_bytes += factor * nbytes

    def merged(self, other: "CollectiveStats", scale: float) -> "CollectiveStats":
        out = CollectiveStats(dict(self.counts), dict(self.raw_bytes),
                              self.wire_bytes)
        for k, v in other.counts.items():
            out.counts[k] = out.counts.get(k, 0) + int(v * scale)
        for k, v in other.raw_bytes.items():
            out.raw_bytes[k] = out.raw_bytes.get(k, 0.0) + v * scale
        out.wire_bytes += other.wire_bytes * scale
        return out


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        if "-done" in line:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3).lower()
        stats.add(kind, _shape_bytes(dtype, dims), _group_size(line))
    return stats


@dataclass
class CostTerms:
    flops: float = 0.0               # global HLO flops (all devices)
    hbm_bytes: float = 0.0           # per-device bytes accessed
    coll: CollectiveStats = field(default_factory=CollectiveStats)

    @staticmethod
    def of(compiled) -> "CostTerms":
        ca = compiled.cost_analysis()
        return CostTerms(
            flops=float(ca.get("flops", 0.0)),
            hbm_bytes=float(ca.get("bytes accessed", 0.0)),
            coll=parse_collectives(compiled.as_text()))

    def extrapolate(self, per_unit: "CostTerms", extra_units: int) -> "CostTerms":
        return CostTerms(
            flops=self.flops + per_unit.flops * extra_units,
            hbm_bytes=self.hbm_bytes + per_unit.hbm_bytes * extra_units,
            coll=self.coll.merged(per_unit.coll, extra_units))

    def diff(self, smaller: "CostTerms") -> "CostTerms":
        d = CollectiveStats()
        d.wire_bytes = max(self.coll.wire_bytes - smaller.coll.wire_bytes, 0.0)
        for k in set(self.coll.counts) | set(smaller.coll.counts):
            d.counts[k] = self.coll.counts.get(k, 0) - smaller.coll.counts.get(k, 0)
            d.raw_bytes[k] = self.coll.raw_bytes.get(k, 0.0) - smaller.coll.raw_bytes.get(k, 0.0)
        return CostTerms(max(self.flops - smaller.flops, 0.0),
                         max(self.hbm_bytes - smaller.hbm_bytes, 0.0), d)


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    t_comp: float
    t_mem: float
    t_coll: float
    model_flops: float
    hlo_flops: float
    bytes_per_device: float
    collective_counts: Dict[str, int]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_comp, "memory": self.t_mem,
                 "collective": self.t_coll}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """How close the step is to the compute roofline: the ideal
        (compute-only) time over the achievable lower-bound time (max of the
        three terms — they overlap at best)."""
        ideal = self.model_flops / (self.chips * meshmod.PEAK_FLOPS_BF16)
        bound = max(self.t_comp, self.t_mem, self.t_coll)
        return ideal / bound if bound else 0.0


def roofline_terms(arch: str, shape: str, mesh_name: str, chips: int,
                   total: CostTerms, model_flops: float,
                   mem_bytes_per_device: float) -> Roofline:
    # cost_analysis flops on an SPMD module are per-device; scale to global
    t_comp = total.flops / meshmod.PEAK_FLOPS_BF16
    t_mem = total.hbm_bytes / meshmod.HBM_BW
    t_coll = total.coll.wire_bytes / meshmod.ICI_BW
    return Roofline(arch, shape, mesh_name, chips, t_comp, t_mem, t_coll,
                    model_flops, total.flops * chips, mem_bytes_per_device,
                    dict(total.coll.counts))


def model_flops_estimate(n_params_active: int, tokens: int, kind: str) -> float:
    """6*N*D for training; 2*N*D for a forward-only (serve) step."""
    return (6.0 if kind == "train" else 2.0) * n_params_active * tokens


def structural_hbm_bytes(run, shape, chips: int) -> float:
    """Per-device HBM traffic estimate assuming TPU-grade fusion (the
    number ``cost_analysis()['bytes accessed']`` approaches only with
    perfect fusion; on the CPU lowering it overcounts 5-10x because every
    HLO op is charged its full operand set and FloatNormalization doubles
    bf16 traffic).  Terms:

      train:   3x params (fwd read, bwd read, update write) + 2x opt state
               + saved layer activations (write + read) + remat recompute
               writes + chunked-CE logits (write+read fwd, recompute bwd)
               + MoE dispatch buffers
      prefill: params + cache write + per-layer activations + CE last pos
      decode:  params + full KV cache read (the decode hot spot)
    """
    import numpy as np
    from repro.models.model import count_params_analytic
    from repro.models.transformer import LM
    import jax, jax.numpy as jnp

    cfg = run.model
    n_params = count_params_analytic(cfg)
    n_active = count_params_analytic(cfg, active_only=True)
    p_bytes = 2 * n_params / chips                      # bf16, fully sharded
    a_bytes_active = 2 * n_active / chips
    dp_shards = max(chips // 16, 1)                     # batch over pod x data
    tokens_local = shape.global_batch * shape.seq_len / dp_shards
    d = cfg.d_model

    model = LM(cfg, param_dtype=jnp.bfloat16)
    cache_dt = jnp.dtype(run.parallel.kv_cache_dtype)
    cache_tree = jax.eval_shape(lambda: model.init_cache(
        shape.global_batch, shape.seq_len, dtype=cache_dt))
    cache_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                      for l in jax.tree.leaves(cache_tree)) / chips

    if shape.kind == "train":
        opt = {"adamw": 8, "adamw_factored": 2.1, "adamw_8bit": 2.1}[
            run.parallel.optimizer_state] * n_params / chips
        acts = cfg.n_layers * tokens_local * d * 2      # saved carries, bf16
        ce = tokens_local * cfg.vocab_size * 4 * 3      # logits w+r fwd, bwd
        moe = 0.0
        if cfg.moe is not None:
            m = cfg.moe
            n_moe_layers = cfg.n_layers - cfg.first_k_dense
            moe = (n_moe_layers * tokens_local * m.top_k * m.capacity_factor
                   * d * 2 * 4)
        return 3 * p_bytes + 2 * a_bytes_active + 2 * opt + 3 * acts + ce + moe
    if shape.kind == "prefill":
        acts = cfg.n_layers * tokens_local * d * 2 * 2
        return a_bytes_active + cache_bytes + acts
    # decode: read every param + the whole cache once per token
    toks = shape.global_batch / dp_shards
    return a_bytes_active + cache_bytes + cfg.n_layers * toks * d * 2 * 8
