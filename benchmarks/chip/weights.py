"""Seeded weights for a dense decoder, made on the device in one jitted
call, in the nested layout the program's parameter tree uses:

    embed/{table, unembed?}
    layers/{ln1/scale, attn/{wq, wk, wv, wo, bq?, bk?, bv?},
            ln2/scale, mlp/{w_up, w_down, w_gate?}}      (stacked over L)
    final_norm/scale

Both the program and the plain reference take their weights from here,
so the reference never reads anything the program made.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import flops

Spec = List[Tuple[Tuple[str, ...], Tuple[int, ...], str, float]]


def spec(m: Dict) -> Spec:
    """(path, shape, init, scale) of every leaf; init is "normal" (scale =
    std), "ones" or "zeros"."""
    d, L, f, v = m["d_model"], m["num_layers"], m["d_ff"], m["vocab_size"]
    hd = flops.head_dim(m)
    nq, nkv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    out: Spec = [(("embed", "table"), (v, d), "normal", 0.02)]
    if not m["tie_embeddings"]:
        out.append((("embed", "unembed"), (d, v), "normal", 1 / math.sqrt(d)))
    out += [
        (("layers", "ln1", "scale"), (L, d), "ones", 0.0),
        (("layers", "attn", "wq"), (L, d, nq), "normal", 1 / math.sqrt(d)),
        (("layers", "attn", "wk"), (L, d, nkv), "normal", 1 / math.sqrt(d)),
        (("layers", "attn", "wv"), (L, d, nkv), "normal", 1 / math.sqrt(d)),
        (("layers", "attn", "wo"), (L, nq, d), "normal",
         1 / math.sqrt(2 * L * nq)),
        (("layers", "ln2", "scale"), (L, d), "ones", 0.0),
        (("layers", "mlp", "w_up"), (L, d, f), "normal", 1 / math.sqrt(d)),
        (("layers", "mlp", "w_down"), (L, f, d), "normal", 1 / math.sqrt(f)),
        (("final_norm", "scale"), (d,), "ones", 0.0),
    ]
    if m["mlp_activation"] == "swiglu":
        out.append((("layers", "mlp", "w_gate"), (L, d, f), "normal",
                    1 / math.sqrt(d)))
    if m.get("qkv_bias"):
        out += [(("layers", "attn", b), (L, n), "zeros", 0.0)
                for b, n in (("bq", nq), ("bk", nkv), ("bv", nkv))]
    return out


def _nest(leaves):
    tree: Dict = {}
    for path, value in leaves:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return tree


def make(m: Dict, seed: int):
    """The whole float32 tree (the type the program trains and serves in)
    from one 31-bit seed, in one jitted call."""
    import jax
    import jax.numpy as jnp

    leaves = spec(m)
    dt = jnp.float32

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape, init, scale) in zip(keys, leaves):
            if init == "ones":
                x = jnp.ones(shape, dt)
            elif init == "zeros":
                x = jnp.zeros(shape, dt)
            else:
                x = scale * jax.random.normal(k, shape, dt)
            out.append((path, x))
        return _nest(out)

    return build(jax.random.key(seed))
