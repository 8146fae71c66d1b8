"""Model assembly: decoder-only LM (dense / MoE / SSM / hybrid blocks),
encoder-decoder (audio), and VLM variants behind one functional ``ModelAPI``.

Layers are parameter-stacked and executed with ``lax.scan`` (+ optional
``jax.checkpoint``), so HLO size and compile time are O(1) in depth — a hard
requirement for the 88-layer mistral-large dry-run on a single CPU host.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (Px, apply_mlp, apply_norm, embed,
                                 init_embedding, init_mlp, init_norm, is_px,
                                 param, softmax_cross_entropy, split_logical,
                                 unembed)
from repro.models.sharding import logical_constraint


# ---------------------------------------------------------------------------
# Stacked-layer helpers
# ---------------------------------------------------------------------------

def init_stack(key, n_layers: int, init_layer: Callable):
    trees = [init_layer(k) for k in jax.random.split(key, n_layers)]

    def stack(*leaves):
        return Px(jnp.stack([l.value for l in leaves]),
                  ("stack",) + tuple(leaves[0].names))

    return jax.tree.map(stack, *trees, is_leaf=is_px)


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention windows; 0 = global.  Shape (L,) int32."""
    L = cfg.num_layers
    if cfg.window_pattern:
        pat = list(cfg.window_pattern)
        ws = [pat[i % len(pat)] for i in range(L)]
    else:
        ws = [cfg.window] * L
    return jnp.asarray(ws, jnp.int32)


def _effective_window(w_scalar, seq_hint: int):
    """Traced per-layer window -> value usable in masks (0 -> no limit)."""
    return jnp.where(w_scalar > 0, w_scalar, jnp.int32(2 ** 30))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": init_norm(ks[0], cfg.d_model, cfg)}
    if cfg.arch_type == "ssm":
        p["mamba"] = ssm_mod.init_mamba(ks[1], cfg)
        return p
    p["attn"] = attn.init_attention(ks[1], cfg)
    p["ln2"] = init_norm(ks[2], cfg.d_model, cfg)
    if cfg.hybrid:
        hd = cfg.resolved_head_dim()
        d_inner = cfg.num_heads * hd
        p["mamba"] = ssm_mod.init_mamba(ks[3], cfg, d_inner=d_inner)
        p["mix_a"] = param(ks[4], (cfg.d_model,), (None,), init="ones")
        p["mix_s"] = param(ks[4], (cfg.d_model,), (None,), init="ones")
    if cfg.num_experts:
        p["moe"] = moe_mod.init_moe(ks[5], cfg)
    else:
        p["mlp"] = init_mlp(ks[5], cfg)
    return p


def _block_fwd(p, h, cfg: ModelConfig, positions, window, impl=None):
    """Full-sequence block.  Returns (h, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.arch_type == "ssm":
        x = apply_norm(p["ln1"], h, cfg)
        return h + ssm_mod.apply_mamba(p["mamba"], x, cfg), aux
    with jax.named_scope("attention"):
        x = apply_norm(p["ln1"], h, cfg)
        a = attn.attention(p["attn"], x, cfg, positions=positions,
                           window=window, impl=impl)
    if cfg.hybrid:
        hd = cfg.resolved_head_dim()
        s = ssm_mod.apply_mamba(p["mamba"], x, cfg,
                                d_inner=cfg.num_heads * hd)
        a = 0.5 * (_chan_norm(a, cfg) * p["mix_a"].astype(a.dtype)
                   + _chan_norm(s, cfg) * p["mix_s"].astype(a.dtype))
    h = h + a
    with jax.named_scope("mlp"):
        x = apply_norm(p["ln2"], h, cfg)
        if cfg.num_experts:
            y, aux = moe_mod.apply_moe(p["moe"], x, cfg)
        else:
            y = apply_mlp(p["mlp"], x, cfg)
    return h + y, aux


def _chan_norm(x, cfg):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + cfg.norm_eps)).astype(x.dtype)


def _block_decode(p, h, cfg: ModelConfig, cache, position, window):
    """One-token block step against the layer cache."""
    if cfg.arch_type == "ssm":
        x = apply_norm(p["ln1"], h, cfg)
        y, new = ssm_mod.decode_mamba(p["mamba"], x, cfg, cache["mamba"])
        return h + y, {"mamba": new}
    new_cache = dict(cache)
    x = apply_norm(p["ln1"], h, cfg)
    w = _effective_window(window, 0)
    a, new_attn = attn.decode_attention(
        p["attn"], x, cfg, cache["attn"], position=position, window=w)
    new_cache["attn"] = new_attn
    if cfg.hybrid:
        hd = cfg.resolved_head_dim()
        s, new_m = ssm_mod.decode_mamba(p["mamba"], x, cfg, cache["mamba"],
                                        d_inner=cfg.num_heads * hd)
        new_cache["mamba"] = new_m
        a = 0.5 * (_chan_norm(a, cfg) * p["mix_a"].astype(a.dtype)
                   + _chan_norm(s, cfg) * p["mix_s"].astype(a.dtype))
    h = h + a
    x = apply_norm(p["ln2"], h, cfg)
    if cfg.num_experts:
        y, _ = moe_mod.apply_moe(p["moe"], x, cfg)
    else:
        y = apply_mlp(p["mlp"], x, cfg)
    return h + y, new_cache


# ---------------------------------------------------------------------------
# Decoder-only LM
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig):
    k_emb, k_layers, k_fin = jax.random.split(key, 3)
    p = {
        "embed": init_embedding(k_emb, cfg),
        "layers": init_stack(k_layers, cfg.num_layers,
                             lambda k: _init_block(k, cfg)),
        "final_norm": init_norm(k_fin, cfg.d_model, cfg),
    }
    if cfg.is_encoder_decoder:
        k_enc, k_cross = jax.random.split(k_emb)
        enc_cfg = cfg
        p["encoder"] = init_stack(
            k_enc, cfg.num_encoder_layers,
            lambda k: {
                "ln1": init_norm(k, cfg.d_model, cfg),
                "attn": attn.init_attention(k, enc_cfg),
                "ln2": init_norm(k, cfg.d_model, cfg),
                "mlp": init_mlp(k, enc_cfg),
            })
        p["enc_norm"] = init_norm(k_enc, cfg.d_model, cfg)
        p["cross"] = init_stack(
            k_cross, cfg.num_layers,
            lambda k: {
                "ln": init_norm(k, cfg.d_model, cfg),
                "attn": attn.init_attention(k, enc_cfg, cross=True),
            })
    return p


def _run_layers(params, h, cfg: ModelConfig, positions, *,
                memory: Optional[jax.Array] = None, impl=None):
    """scan over stacked layers (+ optional cross-attention interleave).

    Uniform-window configs pass the window STATICALLY (enabling the banded
    O(S·W) attention path); heterogeneous ``window_pattern`` configs thread
    per-layer windows through the scan as traced scalars."""
    heterogeneous = bool(cfg.window_pattern)
    windows = layer_windows(cfg) if heterogeneous else None
    static_w = (cfg.window if cfg.window else None) if not heterogeneous \
        else None

    def body(carry, xs):
        if heterogeneous:
            if memory is not None:
                lp, cp, w = xs
            else:
                lp, w = xs
            w = _effective_window(w, h.shape[1])
        else:
            if memory is not None:
                lp, cp = xs
            else:
                lp = xs
            w = static_w
        hh, aux_acc = carry
        hh, aux = _block_fwd_pre_cross(lp, cp, hh, cfg, positions, w,
                                       memory, impl) if memory is not None \
            else _block_fwd(lp, hh, cfg, positions, w, impl)
        return (hh, aux_acc + aux), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    if memory is not None:
        xs = (params["layers"], params["cross"], windows) if heterogeneous \
            else (params["layers"], params["cross"])
    else:
        xs = (params["layers"], windows) if heterogeneous \
            else params["layers"]
    (h, aux), _ = jax.lax.scan(body_fn, (h, jnp.zeros((), jnp.float32)), xs)
    return h, aux


def _block_fwd_pre_cross(lp, cp, h, cfg, positions, w, memory, impl):
    """Decoder block with cross-attention inserted after self-attention."""
    h, aux = _block_fwd_selfattn_only(lp, h, cfg, positions, w, impl)
    x = apply_norm(cp["ln"], h, cfg)
    h = h + attn.attention(cp["attn"], x, cfg, positions=positions,
                           memory=memory)
    x = apply_norm(lp["ln2"], h, cfg)
    y = apply_mlp(lp["mlp"], x, cfg)
    return h + y, aux


def _block_fwd_selfattn_only(p, h, cfg, positions, window, impl):
    x = apply_norm(p["ln1"], h, cfg)
    a = attn.attention(p["attn"], x, cfg, positions=positions, window=window,
                       impl=impl)
    return h + a, jnp.zeros((), jnp.float32)


def encode(params, frames, cfg: ModelConfig):
    """Bidirectional encoder over (stubbed) frame embeddings (B,S,d)."""
    h = frames.astype(cfg.compute_dtype)

    def body(hh, lp):
        x = apply_norm(lp["ln1"], hh, cfg)
        s = attn._project_qkv(lp["attn"], x, x, cfg)
        q, k, v = s
        out = attn._direct(q, k, v, None)
        out = out.reshape(hh.shape[0], hh.shape[1], -1)
        hh = hh + out @ lp["attn"]["wo"].astype(cfg.compute_dtype)
        x = apply_norm(lp["ln2"], hh, cfg)
        return hh + apply_mlp(lp["mlp"], x, cfg), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    h, _ = jax.lax.scan(body_fn, h, params["encoder"])
    return apply_norm(params["enc_norm"], h, cfg)


def forward_hidden(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
                   impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward up to the final norm.  Returns (h, aux_loss)."""
    tokens = batch["tokens"]
    h = embed(params["embed"], tokens, cfg)
    memory = None
    if cfg.is_encoder_decoder:
        memory = encode(params, batch["frames"], cfg)
    if cfg.num_image_tokens:
        patches = batch["patches"].astype(cfg.compute_dtype)
        h = jnp.concatenate([patches, h], axis=1)
        h = logical_constraint(h, "batch", "seq", None)
    positions = jnp.arange(h.shape[1])
    h, aux = _run_layers(params, h, cfg, positions, memory=memory, impl=impl)
    h = apply_norm(params["final_norm"], h, cfg)
    if cfg.num_image_tokens:
        h = h[:, cfg.num_image_tokens:]
    return h, aux


def forward_lm(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
               impl: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits, aux_loss)."""
    h, aux = forward_hidden(params, batch, cfg, impl=impl)
    logits = unembed(params["embed"], h, cfg)
    return logits, aux


def _chunked_ce(params, h, labels, cfg: ModelConfig) -> jax.Array:
    """Cross-entropy without materializing the full (B,S,V) logits: scan
    over sequence chunks, projecting each chunk to the vocab separately.
    Peak logits memory drops S/chunk-fold — the memory-term fix for
    256k-vocab configs (see EXPERIMENTS.md §Perf)."""
    B, S, d = h.shape
    C = min(cfg.loss_chunk, S)
    pad = (-S) % C
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // C
    hc = h.reshape(B, n, C, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, C).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt = carry
        h_i, l_i = xs
        logits = unembed(params["embed"], h_i, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l_i, 0)[..., None], axis=-1)[..., 0]
        ce = lse - gold
        if cfg.z_loss:
            ce = ce + cfg.z_loss * jnp.square(lse)
        valid = (l_i >= 0).astype(jnp.float32)
        return (tot + jnp.sum(ce * valid), cnt + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params, batch, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    if cfg.loss_chunk:
        h, aux = forward_hidden(params, batch, cfg)
        with jax.named_scope("lm_head"):
            ce = _chunked_ce(params, h, batch["labels"], cfg)
    else:
        logits, aux = forward_lm(params, batch, cfg)
        ce = softmax_cross_entropy(logits, batch["labels"], z_loss=cfg.z_loss)
    loss = ce + cfg.router_aux_coef * aux if cfg.num_experts else ce
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, capacity: int,
                      dtype=None) -> Dict[str, Any]:
    """Stacked (L, ...) caches.  ``capacity`` is the KV length for attention
    archs (window size for ring-buffer SWA decode); SSM state is O(1)."""
    L = cfg.num_layers

    def stacked(make):
        one = make()
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (L,) + x.shape), one)

    cache: Dict[str, Any] = {}
    if cfg.arch_type == "ssm":
        cache["mamba"] = stacked(lambda: ssm_mod.init_mamba_cache(cfg, batch, dtype=dtype))
        return cache
    cache["attn"] = stacked(lambda: attn.init_cache(cfg, batch, capacity, dtype=dtype))
    if cfg.hybrid:
        hd = cfg.resolved_head_dim()
        cache["mamba"] = stacked(
            lambda: ssm_mod.init_mamba_cache(cfg, batch,
                                             d_inner=cfg.num_heads * hd,
                                             dtype=dtype))
    if cfg.is_encoder_decoder:
        hd = cfg.resolved_head_dim()
        dt = dtype or cfg.compute_dtype
        cache["cross"] = {
            "k": jnp.zeros((L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dt),
            "v": jnp.zeros((L, batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dt),
        }
    return cache


def decode_step_lm(params, cache, batch, cfg: ModelConfig
                   ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step.  batch: {"token": (B,1) int32, "position": scalar/(B,)}.
    Returns (logits (B,1,V), new_cache)."""
    token, position = batch["token"], batch["position"]
    h = embed(params["embed"], token, cfg)
    windows = layer_windows(cfg)

    if cfg.is_encoder_decoder:
        def body(hh, xs):
            lp, cp, lc, cc, w = xs
            x = apply_norm(lp["ln1"], hh, cfg)
            a, new_attn = attn.decode_attention(lp["attn"], x, cfg, lc,
                                                position=position,
                                                window=_effective_window(w, 0))
            hh = hh + a
            x = apply_norm(cp["ln"], hh, cfg)
            c, _ = attn.decode_attention(cp["attn"], x, cfg, None,
                                         position=position,
                                         memory_cache=cc)
            hh = hh + c
            x = apply_norm(lp["ln2"], hh, cfg)
            hh = hh + apply_mlp(lp["mlp"], x, cfg)
            return hh, new_attn

        h, new_attn = jax.lax.scan(
            body, h, (params["layers"], params["cross"], cache["attn"],
                      cache["cross"], windows))
        new_cache = {"attn": new_attn, "cross": cache["cross"]}
    else:
        def body(hh, xs):
            lp, lc, w = xs
            hh, new = _block_decode(lp, hh, cfg, lc, position, w)
            return hh, new

        layer_cache = {k: v for k, v in cache.items()}
        h, new_cache = jax.lax.scan(body, h, (params["layers"], layer_cache,
                                              windows))
    h = apply_norm(params["final_norm"], h, cfg)
    logits = unembed(params["embed"], h, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching serving path)
# ---------------------------------------------------------------------------

def paged_cache_supported(cfg: ModelConfig) -> bool:
    """The paged pool stores attention K/V only; position-gating cannot mask
    an SSM recurrence (state updates are unconditional), and cross-attention
    memories are per-request, so ssm/hybrid/encoder-decoder archs stay on the
    static-bucket path."""
    return (cfg.arch_type != "ssm" and not cfg.hybrid
            and not cfg.is_encoder_decoder)


def paged_block_bytes(cfg: ModelConfig, block_size: int) -> int:
    """Bytes one physical KV block costs across ALL layers — the unit the
    byte-budget pool sizing and the scheduler's capacity report use.
    Quantized pools pay the narrow payload plus the f32 per-token-per-head
    scale planes."""
    hd = cfg.resolved_head_dim()
    dt = attn.kv_pool_dtype(cfg)
    per_layer = 2 * block_size * cfg.num_kv_heads * hd * dt.itemsize
    if attn.kv_quant_dtype(cfg) is not None:
        per_layer += 2 * block_size * cfg.num_kv_heads * 4
    return cfg.num_layers * per_layer


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=None) -> Dict[str, jax.Array]:
    """A pool of ``num_blocks`` fixed-size KV blocks shared by all serving
    slots, stacked over layers: (L, NB, bs, KV, hd).

    ``cfg.kv_cache_dtype`` picks the storage format: "" / bf16 / f32 pools
    are plain arrays in that dtype ("" = compute dtype, or the ``dtype``
    override); int8 / fp8 / fp8_e5m2 pools store the narrow payload plus
    ``k_scale`` / ``v_scale`` (L, NB, bs, KV) f32 per-token-per-head amax
    scales, quantized on scatter and dequantized on load by the attention
    layer (``dtype`` is ignored — the wire format is the config's)."""
    if not paged_cache_supported(cfg):
        raise NotImplementedError(
            f"paged KV cache unsupported for arch {cfg.arch_type!r} "
            f"(hybrid={cfg.hybrid}, enc-dec={cfg.is_encoder_decoder})")
    hd = cfg.resolved_head_dim()
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads, hd)
    if attn.kv_quant_dtype(cfg) is not None:
        dt = attn.kv_pool_dtype(cfg)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    dt = dtype or (attn.kv_pool_dtype(cfg) if cfg.kv_cache_dtype
                   else cfg.compute_dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _paged_layers(params, h, pool, cfg: ModelConfig, positions, block_table,
                  impl=None):
    """Scan the stacked layers over the paged pool.  h: (S, 1, d);
    positions: (S,); block_table: (S, MB).  Returns (h, new pool).

    Uniform-window configs keep the window STATIC (so the Pallas block-table
    kernel can specialize on it); heterogeneous ``window_pattern`` configs
    thread traced per-layer windows through the scan (jnp path only)."""
    heterogeneous = bool(cfg.window_pattern)
    windows = layer_windows(cfg) if heterogeneous else None
    static_w = None if heterogeneous else (cfg.window or None)
    quantized = "k_scale" in pool
    # XLA CPU moves fp8 arrays through scan slice/stack via per-element
    # convert paths (~70x a 1-byte memcpy); thread fp8 pools through the
    # scan as their uint8 bit patterns and reinterpret inside the body.
    narrow = pool["k"].dtype
    carrier = quantized and narrow in (jnp.float8_e4m3fn, jnp.float8_e5m2)
    pk, pv = pool["k"], pool["v"]
    if carrier:
        pk = jax.lax.bitcast_convert_type(pk, jnp.uint8)
        pv = jax.lax.bitcast_convert_type(pv, jnp.uint8)

    def body(hh, xs):
        if heterogeneous:
            *rest, w = xs
            w = _effective_window(w, 0)
        else:
            rest, w = xs, static_w
        if quantized:
            lp, kc, vc, ks, vs = rest
            if carrier:
                kc = jax.lax.bitcast_convert_type(kc, narrow)
                vc = jax.lax.bitcast_convert_type(vc, narrow)
        else:
            (lp, kc, vc), (ks, vs) = rest, (None, None)
        x = apply_norm(lp["ln1"], hh, cfg)
        out = attn.paged_decode_attention(
            lp["attn"], x, cfg, kc, vc, positions=positions,
            block_table=block_table, window=w, impl=impl,
            k_scale=ks, v_scale=vs)
        a, new_kv = out[0], out[1:]
        if carrier:
            new_kv = (jax.lax.bitcast_convert_type(new_kv[0], jnp.uint8),
                      jax.lax.bitcast_convert_type(new_kv[1], jnp.uint8),
                      ) + tuple(new_kv[2:])
        hh = hh + a
        x = apply_norm(lp["ln2"], hh, cfg)
        if cfg.num_experts:
            y, _ = moe_mod.apply_moe(lp["moe"], x, cfg)
        else:
            y = apply_mlp(lp["mlp"], x, cfg)
        return hh + y, new_kv

    xs = (params["layers"], pk, pv)
    if quantized:
        xs = xs + (pool["k_scale"], pool["v_scale"])
    h, new_kv = jax.lax.scan(body, h, xs + (windows,) if heterogeneous
                             else xs)
    keys = ("k", "v", "k_scale", "v_scale") if quantized else ("k", "v")
    out_pool = dict(zip(keys, new_kv))
    if carrier:
        out_pool["k"] = jax.lax.bitcast_convert_type(out_pool["k"], narrow)
        out_pool["v"] = jax.lax.bitcast_convert_type(out_pool["v"], narrow)
    return h, out_pool


def decode_step_paged(params, pool, batch, cfg: ModelConfig,
                      impl: Optional[str] = None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the active slot set.  batch: {"token": (S,1)
    int32, "position": (S,) int32 (−1 = inactive slot), "block_table":
    (S, MB) int32}.  Returns (logits (S,1,V), new pool)."""
    h = embed(params["embed"], batch["token"], cfg)
    h, pool = _paged_layers(params, h, pool, cfg, batch["position"],
                            batch["block_table"], impl=impl)
    h = apply_norm(params["final_norm"], h, cfg)
    return unembed(params["embed"], h, cfg), pool


def verify_step_paged(params, pool, batch, cfg: ModelConfig,
                      impl: Optional[str] = None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token verification step (speculative decoding / batched
    prefill): every slot feeds up to T scripted tokens at contiguous
    positions and gets logits for ALL of them from ONE forward pass.

    batch: {"tokens": (S, T) int32, "positions": (S, T) int32 — the
    absolute position of each token, −1 for padding tokens and inactive
    slots (live positions must be a contiguous prefix of the row),
    "block_table": (S, MB) int32}.  Returns (logits (S, T, V), new pool).
    """
    h = embed(params["embed"], batch["tokens"], cfg)
    h, pool = _paged_layers(params, h, pool, cfg, batch["positions"],
                            batch["block_table"], impl=impl)
    h = apply_norm(params["final_norm"], h, cfg)
    return unembed(params["embed"], h, cfg), pool


# ---------------------------------------------------------------------------
# Model API
# ---------------------------------------------------------------------------

class ModelAPI(NamedTuple):
    cfg: ModelConfig
    init: Callable            # key -> Px tree
    loss: Callable            # (params, batch) -> (loss, metrics)
    forward: Callable         # (params, batch) -> (logits, aux)
    init_cache: Callable      # (batch, capacity) -> cache
    decode_step: Callable     # (params, cache, batch) -> (logits, cache)
    init_paged_cache: Callable  # (num_blocks, block_size) -> pool
    decode_step_paged: Callable  # (params, pool, batch) -> (logits, pool)
    verify_step_paged: Callable  # (params, pool, batch) -> (logits, pool)


def build_model(cfg: ModelConfig) -> ModelAPI:
    return ModelAPI(
        cfg=cfg,
        init=lambda key: init_lm(key, cfg),
        loss=lambda params, batch: lm_loss(params, batch, cfg),
        forward=lambda params, batch: forward_lm(params, batch, cfg),
        init_cache=lambda batch, capacity, dtype=None: init_decode_cache(
            cfg, batch, capacity, dtype=dtype),
        decode_step=lambda params, cache, batch: decode_step_lm(
            params, cache, batch, cfg),
        init_paged_cache=lambda num_blocks, block_size, dtype=None:
            init_paged_cache(cfg, num_blocks, block_size, dtype=dtype),
        decode_step_paged=lambda params, pool, batch, impl=None:
            decode_step_paged(params, pool, batch, cfg, impl=impl),
        verify_step_paged=lambda params, pool, batch, impl=None:
            verify_step_paged(params, pool, batch, cfg, impl=impl),
    )


def init_params(cfg: ModelConfig, key) -> Tuple[Any, Any]:
    """Materialized (params, logical_names)."""
    tree = init_lm(key, cfg)
    return split_logical(tree)


def abstract_params(cfg: ModelConfig) -> Tuple[Any, Any]:
    """(ShapeDtypeStruct params, logical-name tree) with **no allocation** —
    the dry-run path.  Names are static, so they are captured through the
    eval_shape trace."""
    captured = {}

    def capture(key):
        tree = init_lm(key, cfg)
        params, names = split_logical(tree)
        captured["names"] = names
        return params

    params_sds = jax.eval_shape(capture, jax.random.key(0))
    return params_sds, captured["names"]
