"""Mellum2-12B-A2.5B [hf:JetBrains/Mellum2-12B-A2.5B-Instruct]: 28
layers, every one sparse (64 experts of width 896, top-8, weights
renormalised, no shared expert), GQA 32/4 heads of 128, three sliding
layers (window 1024, default RoPE) then one full layer (YaRN x16 over an
original 8192 positions), both at theta 5e5; untied vocab 98304.  The
MTP head the model card mentions is not in the config and not built."""

from repro.configs.base import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv=4,
    head_dim=128,
    d_ff=896,  # moe_intermediate_size: every layer is sparse
    vocab=98304,
    act="swiglu",
    norm="rms",
    n_experts=64,
    top_k=8,
    norm_topk_prob=True,
    tied_embeddings=False,
    rope_theta=500000.0,
    window=1024,
    layer_types=("sliding", "sliding", "sliding", "full"),
    yarn=Yarn(factor=16.0, original_max_position=8192, beta_fast=32.0,
              beta_slow=1.0, attention_factor=1.2772588722239782),
    remat="dots",
    logits_chunk=512,
    skip_shapes=("long_500k",),  # a 128k context; full layers every 4th
)
