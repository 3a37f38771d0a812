"""deepseek-v2-lite [moe] — 27L d_model=2048 16H vocab=102400.

MLA attention with full-rank queries (kv_lora=512, rope 64 + nope 128, v
128) and YaRN rope scaling (factor 40 over 4,096 positions, mscale 0.707);
one dense layer (d_ff=10944), then 26 MoE layers of 64 routed experts
(d_ff_expert=1408), top-6 softmax gates not renormalised, 2 shared
experts, DeepSeek-V2's per-sequence balance loss.  Untied embedding.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

The expert layer runs DeepSeek-V2's expert-parallel training recipe: a
device keeps at most capacity factor 1.0 of the rows routed to its
experts, dropping the lowest affinities first, and never drops rows of
every tenth sequence first.  ``config()`` is one device holding all 64
experts; the chip benchmark's cut (``chipbench/configs/deepseek-v2-lite.json``)
holds 8 of them.
"""
from repro.common.config import (MLAConfig, ModelConfig, MoEConfig,
                                 ParallelConfig, RunConfig, TrainConfig,
                                 YarnConfig)

#: DeepSeek-V2's aux balance factor for this model (config.json
#: ``aux_loss_alpha``)
AUX_LOSS_ALPHA = 0.001
#: rows r of the global batch with r % 10 == 0 are dropped last (the paper's
#: "~10 % of training sequences")
PROTECTED_EVERY = 10


def config() -> RunConfig:
    return RunConfig(
        model=ModelConfig(
            name="deepseek-v2-lite", family="moe",
            n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
            d_ff=10944, vocab_size=102_400, norm_eps=1e-6,
            mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, rope_head_dim=64,
                          nope_head_dim=128, v_head_dim=128,
                          yarn=YarnConfig(factor=40, original_max_position_embeddings=4096,
                                          beta_fast=32, beta_slow=1,
                                          mscale=0.707, mscale_all_dim=0.707)),
            moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                          num_shared_experts=2, norm_topk_prob=False,
                          seq_aux=True, load_balance_loss=AUX_LOSS_ALPHA,
                          router_z_loss=0.0, capacity_factor=1.0,
                          experts_held=64, protected_every=PROTECTED_EVERY),
            first_k_dense=1, tie_embeddings=False,
        ),
        parallel=ParallelConfig(remat="full", microbatches=2),
        train=TrainConfig(),
    )


def smoke_config() -> RunConfig:
    """Every mechanism at CPU size: a share of 2 of 16 experts on its
    budget, YaRN on 8 rope dims."""
    return RunConfig(
        model=ModelConfig(
            name="deepseek-lite-smoke", family="moe",
            n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
            d_ff=160, vocab_size=512,
            mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, rope_head_dim=8,
                          nope_head_dim=16, v_head_dim=16,
                          yarn=YarnConfig(factor=40, original_max_position_embeddings=4096,
                                          beta_fast=32, beta_slow=1,
                                          mscale=0.707, mscale_all_dim=0.707)),
            moe=MoEConfig(num_experts=16, top_k=3, d_ff_expert=32,
                          num_shared_experts=2, norm_topk_prob=False,
                          seq_aux=True, load_balance_loss=AUX_LOSS_ALPHA,
                          router_z_loss=0.0, capacity_factor=1.0,
                          experts_held=2, protected_every=PROTECTED_EVERY),
            first_k_dense=1, tie_embeddings=False,
        ),
        parallel=ParallelConfig(remat="none"),
        train=TrainConfig(seq_len=32, global_batch=2),
    )
