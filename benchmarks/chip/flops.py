"""Operation counts the benchmark divides by measured time.

One convention for every cell, stated once:

* a matmul of (M, K) by (K, N) is 2·M·N·K operations;
* attention scores and their weighted sum cost 2·2·(heads·head_dim) per
  query per attended key, and training counts every key of the sequence
  (no halving for the causal mask: the program computes the full masked
  score matrix);
* training is forward + backward = 3 x forward; recomputation (remat)
  and the optimizer (Muon's Newton-Schulz, AdamW) are not counted;
* the input embedding lookup is a gather, not a matmul; a tied table
  counts once, as the LM head.

Sizes come from the configuration file's ``run_as`` block, never from the
program.
"""
from __future__ import annotations

from typing import Dict


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def param_count(m: Dict) -> int:
    """Every parameter of the dense decoder the ``run_as`` block states."""
    d, hd, L = m["d_model"], head_dim(m), m["num_layers"]
    nq, nkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    attn = d * nq + 2 * d * nkv + nq * d
    if m.get("qkv_bias"):
        attn += nq + 2 * nkv
    mlp = (3 if m["mlp_activation"] == "swiglu" else 2) * d * m["d_ff"]
    per_layer = attn + mlp + 2 * d                       # two norm scales
    emb = m["vocab_size"] * d
    return L * per_layer + (emb if m["tie_embeddings"] else 2 * emb) + d


def matmul_params(m: Dict) -> int:
    """Parameters that take part in a matmul per token: all of them but an
    untied input embedding table (a lookup)."""
    untied_table = 0 if m["tie_embeddings"] else m["vocab_size"] * m["d_model"]
    return param_count(m) - untied_table


def attn_width(m: Dict) -> int:
    return m["num_layers"] * m["num_heads"] * head_dim(m)


def train_flops_per_token(m: Dict, seq: int) -> float:
    """6·N + 12·L·H·D·S: forward + backward, every key of the sequence."""
    return 6.0 * matmul_params(m) + 12.0 * attn_width(m) * seq
