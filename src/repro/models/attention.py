"""Grouped-query attention with rotary embeddings, sliding windows and KV
caches (full + ring-buffer) — the reference jnp implementation.

Four execution paths, selected per call by ``select_impl`` from what the
call can observe (backend, shapes, causality, whether the window is static):

* ``_flash``    — the Pallas flash kernel (``kernels/flash_attention``),
                  forward and backward, score tiles kept in VMEM; causal
                  self-attention with a static window on a TPU.
* ``_direct``   — materialized scores; short sequences (train_4k, decode).
* ``_blocked``  — lax.scan over KV chunks with an online softmax (the pure-jnp
                  mirror of the Pallas flash kernel); long prefill.
* ``_banded``   — sliding-window prefill that only gathers the W-wide band of
                  keys per query block: O(S·W) instead of O(S²) FLOPs.

GQA is computed grouped — queries reshaped to (B,S,KV,G,D) — so KV heads are
never materialized repeated.  All tensors carry logical sharding annotations;
when a head count does not divide the tensor-parallel degree the constraint
silently relaxes (see models/sharding.py).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, param
from repro.models.sharding import logical_constraint

NEG_INF = -1e30

# ``ModelConfig.kv_cache_dtype`` spellings -> kernels.quantize target names
# (None = plain narrow cast, no scales)
KV_QUANT_TARGETS = {"int8": "int8", "fp8": "fp8_e4m3",
                    "fp8_e4m3": "fp8_e4m3", "fp8_e5m2": "fp8_e5m2"}
_KV_PLAIN = {"": None, "bf16": "bfloat16", "bfloat16": "bfloat16",
             "f32": "float32", "float32": "float32"}


def kv_quant_dtype(cfg: ModelConfig) -> Optional[str]:
    """The quantize-kernel target name the config's KV pool uses, or None
    for an unquantized (plain-dtype) pool."""
    s = cfg.kv_cache_dtype
    if s in _KV_PLAIN:
        return None
    if s not in KV_QUANT_TARGETS:
        raise ValueError(f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r}; "
                         f"expected one of {sorted(_KV_PLAIN)} or "
                         f"{sorted(KV_QUANT_TARGETS)}")
    return KV_QUANT_TARGETS[s]


def kv_pool_dtype(cfg: ModelConfig):
    """Storage dtype of the paged pool's k/v arrays."""
    qd = kv_quant_dtype(cfg)
    if qd is not None:
        from repro.kernels.quantize import target_dtype
        return jnp.dtype(target_dtype(qd))
    return jnp.dtype(_KV_PLAIN[cfg.kv_cache_dtype] or cfg.compute_dtype)


# Force a particular implementation (tests / perf experiments); None = auto.
FORCE_IMPL: Optional[str] = None
# Above this KV length the blocked/banded paths are used.
DIRECT_MAX_KV = 4096
BLOCK_Q = 512
BLOCK_KV = 1024
# Flash kernel tiles, largest first: the first that divides S is used.  At
# S=2048 on a v5e, 1024-wide tiles beat 512 (fewer grid steps outweigh the
# causal waste: 3 of 4 tile pairs computed against 10 of 16); they compile
# within VMEM for head dims up to 256.
FLASH_BLOCKS = (1024, 512, 256, 128)


def flash_block(S: int) -> int:
    """The flash tile (query and key block) for length S."""
    return next(b for b in FLASH_BLOCKS if S % b == 0)


def select_impl(*, backend: str, S: int, Sk: int, causal: bool,
                static_window: bool, window: Optional[int],
                fp8: bool) -> str:
    """The attention path for one call.  A pure function of what the call
    can observe: the flash kernel takes causal self-attention with a static
    window on a TPU when S is a multiple of a flash block; everything else
    (and every call on a CPU) keeps the jnp paths."""
    if (backend == "tpu" and causal and S == Sk and static_window
            and not fp8 and S % FLASH_BLOCKS[-1] == 0):
        return "flash"
    if (static_window and window and window < Sk and Sk > DIRECT_MAX_KV
            and causal and S == Sk and S % min(BLOCK_Q, S) == 0):
        return "banded"
    if Sk > DIRECT_MAX_KV:
        return "blocked"
    return "direct"


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 4)
    o_scale = 1.0 / math.sqrt(2 * max(cfg.num_layers, 1) * nq)
    p = {
        "wq": param(ks[0], (d, nq), ("fsdp", "heads")),
        "wk": param(ks[1], (d, nkv), ("fsdp", "kv_heads")),
        "wv": param(ks[2], (d, nkv), ("fsdp", "kv_heads")),
        "wo": param(ks[3], (nq, d), ("heads", "fsdp"), scale=o_scale),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = param(ks[0], (nq,), (None,), init="zeros")
        p["bk"] = param(ks[1], (nkv,), (None,), init="zeros")
        p["bv"] = param(ks[2], (nkv,), (None,), init="zeros")
    return p


def _project_qkv(p, xq, xkv, cfg: ModelConfig):
    dt = cfg.compute_dtype
    hd = cfg.resolved_head_dim()
    q = xq @ p["wq"].astype(dt)
    k = xkv @ p["wk"].astype(dt)
    v = xkv @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = logical_constraint(q, "batch", "seq", "heads")
    k = logical_constraint(k, "batch", "seq", "kv_heads")
    v = logical_constraint(v, "batch", "seq", "kv_heads")
    B, Sq = q.shape[:2]
    Skv = k.shape[1]
    q = q.reshape(B, Sq, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, hd)
    k = k.reshape(B, Skv, cfg.num_kv_heads, hd)
    v = v.reshape(B, Skv, cfg.num_kv_heads, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# Score paths
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, window, causal: bool):
    """Additive mask bias (…, Sq, Sk) from position arrays."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = k_pos[..., None, :] >= 0
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _direct(q, k, v, bias):
    """q: (B,Sq,KV,G,D), k/v: (B,Sk,KV,D), bias: (B,1,1,Sq,Sk) or None."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v)


def _flash(q, k, v, window):
    """Causal self-attention through the Pallas flash kernel; query i and
    key j sit at positions i and j.  q: (B,S,KV,G,D), k/v: (B,S,KV,D).
    Every matmul takes bfloat16 operands and accumulates in float32."""
    from repro.kernels.flash_attention import flash_attention
    B, S, KV, G, D = q.shape
    blk = flash_block(S)
    qh = q.reshape(B, S, KV * G, D).transpose(0, 2, 1, 3)     # (B,H,S,D)
    o = flash_attention(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        causal=True, window=window, bq=blk, bk=blk,
                        dot_dtype=jnp.bfloat16)
    return o.transpose(0, 2, 1, 3).reshape(B, S, KV, G, D)


def _blocked(q, k, v, q_pos, k_pos, window, causal):
    """Online-softmax scan over KV chunks (flash-attention in jnp)."""
    B, Sq, KV, G, D = q.shape
    Sk = k.shape[1]
    bk = min(BLOCK_KV, Sk)
    nblocks = (Sk + bk - 1) // bk
    pad = nblocks * bk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    kc = k.reshape(B, nblocks, bk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nblocks, bk, KV, D).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(B, nblocks, bk).transpose(1, 0, 2)
    scale = 1.0 / math.sqrt(D)

    def step(carry, blk):
        m, l, acc = carry
        kb, vb, pb = blk
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kb).astype(jnp.float32) * scale
        s = s + _mask_bias(q_pos, pb, window, causal)[:, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)  # (B,Sq,KV,G,D)


def _banded(q, k, v, window, cfg):
    """Sliding-window prefill: per query block gather only the (W + Bq)-wide
    key band.  FLOPs O(S·(W+Bq)) — the sub-quadratic dense-arch path."""
    B, Sq, KV, G, D = q.shape
    bq = min(BLOCK_Q, Sq)
    nq = Sq // bq
    assert Sq % bq == 0, "banded path expects block-aligned sequence"
    band = window + bq
    scale = 1.0 / math.sqrt(D)
    # pad keys on the left so every band gather is in-bounds
    kp = jnp.pad(k, ((0, 0), (band - bq, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (band - bq, 0), (0, 0), (0, 0)))

    def block(i):
        q0 = i * bq
        qb = jax.lax.dynamic_slice_in_dim(q, q0, bq, axis=1)
        kb = jax.lax.dynamic_slice_in_dim(kp, q0, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, q0, band, axis=1)
        q_pos = q0 + jnp.arange(bq)
        k_pos = q0 - (band - bq) + jnp.arange(band)  # may be negative -> masked
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb).astype(jnp.float32) * scale
        s = s + _mask_bias(q_pos, k_pos, window, True)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vb)

    outs = jax.lax.map(block, jnp.arange(nq))          # (nq, B, bq, KV, G, D)
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, D)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=None) -> Dict[str, Any]:
    """A fixed-capacity cache.  For full attention capacity = max_seq_len; for
    sliding-window decode it is the window (ring buffer)."""
    hd = cfg.resolved_head_dim()
    dt = dtype or cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, capacity, cfg.num_kv_heads, hd), dt),
        "v": jnp.zeros((batch, capacity, cfg.num_kv_heads, hd), dt),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),   # next write slot (mod capacity)
    }


def cache_logical_names(ring: bool = False):
    return {"k": ("batch", "seq", "kv_heads", None),
            "v": ("batch", "seq", "kv_heads", None),
            "pos": ("batch", "seq"),
            "idx": ()}


def _cache_insert(cache, k_new, v_new, pos_new):
    """Insert S_new entries at idx mod capacity.  Decode writes a single
    position, so a ring write never crosses the buffer boundary; prefill
    writes start at slot 0.  Functional (returns a new cache pytree)."""
    cap = cache["k"].shape[1]
    s_new = k_new.shape[1]
    slot = jnp.mod(cache["idx"], cap)

    def upd(buf, new):
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), slot, axis=1)

    return {"k": upd(cache["k"], k_new), "v": upd(cache["v"], v_new),
            "pos": upd(cache["pos"], pos_new), "idx": cache["idx"] + s_new}


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def attention(p, x, cfg: ModelConfig, *, positions: jax.Array,
              window: Optional[int] = None,
              memory: Optional[jax.Array] = None,
              impl: Optional[str] = None) -> jax.Array:
    """Training / prefill attention.  ``memory`` switches to cross-attention
    (bidirectional over the encoder output).  ``impl`` forces a path
    ("flash", "direct", "blocked", "banded"); None lets ``select_impl``
    choose.  Self-attention runs over ``positions`` 0..S-1."""
    B, S = x.shape[:2]
    cross = memory is not None
    xkv = memory if cross else x
    q, k, v = _project_qkv(p, x, xkv, cfg)
    if not cross:
        q = apply_rope(q.reshape(B, S, cfg.num_heads, -1), positions,
                       cfg.rope_theta).reshape(q.shape)
        k = apply_rope(k, positions, cfg.rope_theta)
    k_pos = (jnp.broadcast_to(jnp.arange(xkv.shape[1]), (B, xkv.shape[1]))
             if cross else jnp.broadcast_to(positions, (B, S)))
    q_pos = jnp.broadcast_to(positions, (B, S))
    static_window = isinstance(window, int) or window is None
    if static_window:
        w = None if not window else window
    else:
        w = window  # traced per-layer window (0 already mapped to "huge")
    causal = not cross
    Sk = xkv.shape[1]
    mode = impl or FORCE_IMPL or select_impl(
        backend=jax.default_backend(), S=S, Sk=Sk, causal=causal,
        static_window=static_window, window=w, fp8=cfg.fp8_matmul)
    if mode == "flash":
        if not (causal and S == Sk and static_window):
            raise ValueError("impl='flash' takes causal self-attention "
                             "with a static window")
        with jax.named_scope("flash_attention"):
            out = _flash(q, k, v, w)
    elif mode == "banded":
        out = _banded(q, k, v, w, cfg)
    elif mode == "blocked":
        out = _blocked(q, k, v, q_pos, k_pos, w, causal)
    else:
        bias = _mask_bias(q_pos, k_pos, w, causal)[:, None, None]
        out = _direct(q, k, v, bias)
    out = out.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim())
    out = logical_constraint(out, "batch", "seq", "heads")
    y = out @ p["wo"].astype(cfg.compute_dtype)
    return logical_constraint(y, "batch", "seq", None)


def decode_attention(p, x, cfg: ModelConfig, cache: Dict[str, Any], *,
                     position: jax.Array, window: Optional[int] = None,
                     memory_cache: Optional[Dict[str, jax.Array]] = None
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One-token decode against a KV cache.

    x: (B, 1, d).  position: scalar or (B,) absolute position of the new
    token.  ``memory_cache`` holds precomputed cross-attention K/V.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim()
    if memory_cache is not None:   # cross-attention: read-only memory
        dt = cfg.compute_dtype
        q = (x @ p["wq"].astype(dt)).reshape(
            B, 1, cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, hd)
        k, v = memory_cache["k"], memory_cache["v"]
        out = _direct(q, k, v, None)
        out = out.reshape(B, 1, cfg.num_heads * hd)
        y = out @ p["wo"].astype(dt)
        return logical_constraint(y, "batch", "seq", None), cache
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    pos = jnp.broadcast_to(jnp.asarray(position, jnp.int32).reshape(-1, 1)
                           if jnp.ndim(position) else
                           jnp.asarray(position, jnp.int32), (B, 1))
    q = apply_rope(q.reshape(B, 1, cfg.num_heads, hd), pos, cfg.rope_theta
                   ).reshape(q.shape)
    k_new = apply_rope(k_new, pos, cfg.rope_theta)
    cache = _cache_insert(cache, k_new, v_new, pos)
    if isinstance(window, int) and window == 0:
        window = None
    bias = _mask_bias(pos, cache["pos"], window, True)
    out = _direct(q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype),
                  bias[:, None, None])
    out = out.reshape(B, 1, cfg.num_heads * hd)
    out = logical_constraint(out, "batch", "seq", "heads")
    y = out @ p["wo"].astype(cfg.compute_dtype)
    return logical_constraint(y, "batch", "seq", None), cache


def paged_decode_attention(p, x, cfg: ModelConfig, k_pool: jax.Array,
                           v_pool: jax.Array, *, positions: jax.Array,
                           block_table: jax.Array,
                           window: Optional[jax.Array] = None,
                           impl: Optional[str] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None):
    """Decode / verify attention against a *paged* KV pool shared by all
    slots.

    x: (S, T, d) — T fresh tokens per serving slot (T = 1 for plain decode;
    T > 1 for speculative verification / multi-token prefill, where a slot's
    tokens occupy *contiguous* positions); k_pool/v_pool: (NB, bs, KV, hd)
    fixed-size physical blocks; positions: (S,) int32 when T == 1, else
    (S, T) int32 — absolute position each token is written at / queries
    from.  −1 marks an inactive slot (T == 1) or a padding token (T > 1):
    its write is dropped and its output row is garbage the caller must
    ignore.  When T > 1 the live positions of a slot must be a contiguous
    prefix ``start .. start + n − 1`` of the row (the padded-script layout
    the engine emits); block_table: (S, MB) int32 physical block ids
    (−1 = unmapped).

    Blocks hold contiguous positions (slot s's logical position i lives at
    offset i % bs of physical block ``block_table[s, i // bs]``), so validity
    is purely positional: lane i is attendable iff ``i <= position of the
    query token`` and its table entry is mapped — the position-gated mask
    that lets slots at different generation depths coexist in one batched
    step.  All T fresh K/V are scattered before the attention reads, so
    causality *among* the T tokens is the same positional gate.

    Quantized pools (``cfg.kv_cache_dtype`` int8/fp8/fp8_e5m2) carry
    ``k_scale``/``v_scale``: (NB, bs, KV) f32 per-token-per-head amax
    scales.  Fresh K/V are quantized along the head dim on scatter
    (``kernels.quantize.reference_quantize_axis``) and dequantized on load
    — jnp path inline, Pallas path via the ``*_dequant`` kernel variants.

    ``cfg.fp8_matmul`` runs the plain-pool Pallas kernels' QK^T on per-row
    fp8 tiles; the dequant variants keep the f32 contraction (their K rows
    are already one narrow cast deep — a second quantization would compound
    the error for no bandwidth win, since the payload is narrow in memory).

    Returns (y (S, T, d), new_k_pool, new_v_pool) for plain pools, plus
    (new_k_scale, new_v_scale) when the pool is quantized.
    """
    S, T = x.shape[:2]
    hd = cfg.resolved_head_dim()
    NB, bs = k_pool.shape[:2]
    MB = block_table.shape[1]
    q, k_new, v_new = _project_qkv(p, x, x, cfg)
    pos = jnp.asarray(positions, jnp.int32)
    if pos.ndim == 1:                                          # (S,) -> (S,T)
        assert T == 1, "1-d positions require a single token per slot"
        pos = pos[:, None]
    active = pos >= 0                                          # (S, T)
    posc = jnp.maximum(pos, 0)
    q = apply_rope(q.reshape(S, T, cfg.num_heads, hd), posc,
                   cfg.rope_theta).reshape(q.shape)
    k_new = apply_rope(k_new, posc, cfg.rope_theta)

    # -- scatter the fresh K/V into the pool (inactive writes fall out of
    # bounds and are dropped) ------------------------------------------------
    col = posc // bs                                           # (S, T)
    blk = jnp.take_along_axis(block_table, col, axis=1)        # (S, T)
    dest = blk * bs + posc % bs
    dest = jnp.where(active & (blk >= 0), dest, NB * bs)       # OOB sentinel
    quantized = k_scale is not None
    if quantized:
        from repro.kernels.quantize import reference_quantize_axis
        qd = kv_quant_dtype(cfg)
        k_w, k_s = reference_quantize_axis(k_new, axis=-1, dtype=qd)
        v_w, v_s = reference_quantize_axis(v_new, axis=-1, dtype=qd)
        ks_flat = k_scale.reshape(NB * bs, cfg.num_kv_heads)
        vs_flat = v_scale.reshape(NB * bs, cfg.num_kv_heads)
        ks_flat = ks_flat.at[dest.reshape(-1)].set(
            k_s.reshape(S * T, cfg.num_kv_heads), mode="drop")
        vs_flat = vs_flat.at[dest.reshape(-1)].set(
            v_s.reshape(S * T, cfg.num_kv_heads), mode="drop")
        new_ks = ks_flat.reshape(NB, bs, cfg.num_kv_heads)
        new_vs = vs_flat.reshape(NB, bs, cfg.num_kv_heads)
    else:
        k_w, v_w = k_new, v_new
        new_ks = new_vs = None
    k_flat = k_pool.reshape(NB * bs, cfg.num_kv_heads, hd)
    v_flat = v_pool.reshape(NB * bs, cfg.num_kv_heads, hd)
    k_flat = k_flat.at[dest.reshape(-1)].set(
        k_w.reshape(S * T, cfg.num_kv_heads, hd).astype(k_flat.dtype),
        mode="drop")
    v_flat = v_flat.at[dest.reshape(-1)].set(
        v_w.reshape(S * T, cfg.num_kv_heads, hd).astype(v_flat.dtype),
        mode="drop")
    new_k = k_flat.reshape(NB, bs, cfg.num_kv_heads, hd)
    new_v = v_flat.reshape(NB, bs, cfg.num_kv_heads, hd)

    static_window = isinstance(window, int) or window is None
    if isinstance(window, int) and window == 0:
        window = None
    if impl == "pallas" and static_window and T == 1 and not quantized:
        from repro.kernels.decode_attention import \
            paged_decode_attention as paged_kernel
        out = paged_kernel(q[:, 0], new_k.astype(q.dtype),
                           new_v.astype(q.dtype), block_table,
                           jnp.where(active[:, 0], pos[:, 0], -1),
                           window=window or 0,
                           fp8=cfg.fp8_matmul)[:, None]         # (S,1,KV,G,hd)
    elif impl == "pallas" and static_window and not quantized:
        from repro.kernels.decode_attention import \
            paged_verify_attention as verify_kernel
        # live tokens are a contiguous prefix: recover (start, n) per slot
        start = jnp.where(active[:, 0], pos[:, 0], -1)
        n_tok = jnp.sum(active.astype(jnp.int32), axis=1)
        out = verify_kernel(q, new_k.astype(q.dtype), new_v.astype(q.dtype),
                            block_table, start, n_tok, window=window or 0,
                            fp8=cfg.fp8_matmul)
    elif impl == "pallas" and static_window and T == 1:
        from repro.kernels.decode_attention import \
            paged_decode_attention_dequant as paged_dq_kernel
        out = paged_dq_kernel(q[:, 0], new_k, new_v, new_ks, new_vs,
                              block_table,
                              jnp.where(active[:, 0], pos[:, 0], -1),
                              window=window or 0)[:, None]
    elif impl == "pallas" and static_window:
        from repro.kernels.decode_attention import \
            paged_verify_attention_dequant as verify_dq_kernel
        start = jnp.where(active[:, 0], pos[:, 0], -1)
        n_tok = jnp.sum(active.astype(jnp.int32), axis=1)
        out = verify_dq_kernel(q, new_k, new_v, new_ks, new_vs, block_table,
                               start, n_tok, window=window or 0)
    else:
        safe = jnp.maximum(block_table, 0)                     # (S, MB)
        if quantized:       # dequant-on-load: payload x per-token-head scale
            from repro.kernels.quantize import fast_dequant_cast
            # dequantize the WHOLE pool once (table-gather convert), then
            # block-gather f32: XLA CPU lowers a per-element fp8 convert
            # fused after the block gather to software emulation, which
            # dominates the step.  This jnp path only serves CPU/test
            # runs — the Pallas dequant kernels own the accelerator path,
            # fusing the convert into the tile load instead.
            kf = fast_dequant_cast(new_k) * new_ks[..., None]
            vf = fast_dequant_cast(new_v) * new_vs[..., None]
            k_all = kf[safe].reshape(S, MB * bs, cfg.num_kv_heads,
                                     hd).astype(q.dtype)
            v_all = vf[safe].reshape(S, MB * bs, cfg.num_kv_heads,
                                     hd).astype(q.dtype)
        else:
            k_all = new_k[safe].reshape(S, MB * bs, cfg.num_kv_heads, hd)
            v_all = new_v[safe].reshape(S, MB * bs, cfg.num_kv_heads, hd)
        k_pos = jnp.broadcast_to(jnp.arange(MB * bs), (S, MB * bs))
        mapped = jnp.repeat(block_table >= 0, bs, axis=1)
        k_pos = jnp.where(mapped, k_pos, -1)
        bias = _mask_bias(pos, k_pos, window, True)            # (S, T, L)
        out = _direct(q, k_all.astype(q.dtype), v_all.astype(q.dtype),
                      bias[:, None, None])
    out = out.reshape(S, T, cfg.num_heads * hd)
    out = logical_constraint(out, "batch", "seq", "heads")
    y = out @ p["wo"].astype(cfg.compute_dtype)
    y = logical_constraint(y, "batch", "seq", None)
    if quantized:
        return y, new_k, new_v, new_ks, new_vs
    return y, new_k, new_v


def precompute_cross_cache(p, memory: jax.Array, cfg: ModelConfig):
    """K/V for cross-attention, computed once per request."""
    dt = cfg.compute_dtype
    B, S = memory.shape[:2]
    hd = cfg.resolved_head_dim()
    k = (memory @ p["wk"].astype(dt)).reshape(B, S, cfg.num_kv_heads, hd)
    v = (memory @ p["wv"].astype(dt)).reshape(B, S, cfg.num_kv_heads, hd)
    return {"k": k, "v": v}
