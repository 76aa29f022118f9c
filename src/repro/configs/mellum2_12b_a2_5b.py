"""Mellum2-12B-A2.5B-Instruct [hf:JetBrains/Mellum2-12B-A2.5B-Instruct,
config.json]: 28L, d_model 2304, 32H GQA kv=4 of 128; every MLP sparse,
64 experts of width 896, top-8 renormalised (norm_topk_prob), no shared
expert; layers repeat (sliding, sliding, sliding, full) with window 1024;
rope theta 500000, YaRN on the full layers (factor 16 over 8192 original
positions, beta_fast 32, beta_slow 1, attention factor
1.2772588722239782), plain rope on the sliding ones; vocab 98304, untied
head, RMSNorm eps 1e-6.  The MTP head the model card mentions has no key
in config.json and is left out.  All 64 experts are held: the held-expert
layer uncut (no capacity, no drop)."""

from repro.models.config import ModelConfig, Rope

THETA = 500_000.0


def config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        num_layers=28,
        d_model=2304,
        vocab_size=98_304,
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        mlp="moe",
        num_experts=64,
        experts_held=64,
        moe_top_k=8,
        moe_d_ff=896,
        moe_group_size=4096,
        block_pattern=("local", "local", "local", "attn"),
        local_window=1024,
        window=None,
        rope_theta=THETA,
        rope_by_kind=(("attn", Rope(
            THETA, yarn_factor=16.0, yarn_original_len=8192,
            yarn_beta_fast=32.0, yarn_beta_slow=1.0,
            yarn_attention_factor=1.2772588722239782)),),
        norm_eps=1e-6,
    )
