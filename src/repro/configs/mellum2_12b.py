"""mellum2-12b-a2.5b [moe] — 64 experts top-8, sliding and full attention
layers 3:1, YaRN on the full layers
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]."""
from repro.configs.base import ModelConfig

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    arch_type="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,                 # per-expert FFN width (moe_intermediate_size)
    vocab_size=98304,
    n_experts=64,
    top_k=8,
    capacity_factor=8.0,      # = n_experts / top_k: dropless, as published
    rope_theta=5.0e5,
    yarn_factor=16.0,         # beta_fast 32, beta_slow 1 and attention
    yarn_original_max_pos=8192,  # factor 0.1 ln 16 + 1, as published
    norm_eps=1.0e-6,
    sliding_window=1024,
    layer_types=_PERIOD * 7,
    param_dtype="bfloat16",
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
