"""nanochat d20 — the paper's own reference model (~561M params, 20 layers).

[github.com/karpathy/nanochat — depth-20 config: d_model = 64*depth = 1280,
 10 heads of 128, MLP c_fc/c_proj at 4x with relu^2, vocab 2^16, rotary,
 untied embeddings]

What nanochat has that this config does not: the logit softcap of 15 on
the LM head (``logit_soft_cap`` stays 0 here) and QK-norm (RMSNorm of
queries and keys before rotary; the attention layer has no such norm).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="nanochat-d20",
    arch_type="dense",
    source="github:karpathy/nanochat (d20 speedrun config)",
    num_layers=20,
    d_model=1280,
    num_heads=10,
    num_kv_heads=10,
    head_dim=128,
    d_ff=5120,
    vocab_size=65536,
    mlp_activation="relu2",
    rope_theta=10000.0,
    tie_embeddings=False,
)
