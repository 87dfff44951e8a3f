"""qwen3-moe-30b-a3b [moe] — 48L d_model=2048 32H (GQA kv=4) head_dim=128,
128 routed experts of width 768, top-8 renormalized, no shared expert,
every layer sparse; q/k norm, untied head, vocab=151936
[hf:Qwen/Qwen3-30B-A3B config.json].

``n_experts`` is the router's width; ``n_experts_held`` (0 here: all of
them) and ``expert_offset`` say which experts a layer holds, for a chip's
share of an expert-parallel deployment."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    # the width of a dense MLP layer, which this model has none of
    # (decoder_sparse_step 1, mlp_only_layers []): unused, so set to the
    # expert width; the published intermediate_size, 6144, is unused too
    d_ff=768,
    moe_d_ff=768,           # moe_intermediate_size: each expert's width
    vocab_size=151_936,
    n_experts=128,
    n_experts_per_token=8,
    qk_norm=True,
    rope_theta=1_000_000.0,
)
