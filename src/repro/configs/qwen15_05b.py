"""qwen1.5-0.5b  [hf:Qwen/Qwen1.5-0.5B]
dense, 24L, d_model=1024, 16 heads (MHA: kv=16), d_ff=2816, vocab=151936,
QKV bias, tied embeddings.

[huggingface.co/Qwen/Qwen1.5-0.5B config.json, Qwen2ForCausalLM: hidden
1024, 24 layers, 16 heads of 64 with 16 KV heads, SwiGLU intermediate 2816,
vocab 151936 tied, rope_theta 1e6, rms_norm_eps 1e-6]

Where the program departs from the published keys:

* no sliding window: ``window`` stays 0, full attention, as the published
  ``use_sliding_window: false`` runs it (its ``sliding_window`` of 32,768
  is not carried);
* no maximum context: the published ``max_position_embeddings`` is 32,768,
  the program's rotary positions work at any length, and the training cell
  runs 2,048-token rows.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen1.5-0.5b",
    arch_type="dense",
    source="hf:Qwen/Qwen1.5-0.5B",
    num_layers=24,
    d_model=1024,
    num_heads=16,
    num_kv_heads=16,
    head_dim=64,
    d_ff=2816,
    vocab_size=151936,
    qkv_bias=True,
    mlp_activation="swiglu",
    rope_theta=1000000.0,
    norm_eps=1e-6,
    tie_embeddings=True,
)
