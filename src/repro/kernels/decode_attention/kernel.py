"""Single-token GQA decode attention — Pallas TPU kernel (the serving
hot-spot: one query against a long KV cache).

Differences from the prefill flash kernel:

* Sq = 1: the query tile is a (G, D) block (all heads of one KV group),
  so the MXU contraction is (G, D) x (D, BK) — head-dim contraction keeps
  the systolic array busy even with a single token;
* the cache may be a ring buffer: validity comes from an explicit per-slot
  ``pos`` array (−1 = empty, else absolute position), with causal +
  sliding-window predicates evaluated against the query's position —
  layout-free, so prefill-then-wrap caches need no compaction;
* grid = (B, KV, S/BK): the KV-block sweep is minor-most, so the online
  softmax state (m, l, acc) lives in VMEM scratch across the sweep.

Validated in interpret mode against ``ref.reference_decode_attention``.

The paged kernels (decode, verify, and their quantized-pool variants)
share one body, ``_paged_kernel``: grid = (slots, logical blocks), each
step DMAs a whole physical pool block (bs, KV, D) named by the block
table and sweeps the KV heads inside the body.  Single-token decode is
the T = 1 case of verify.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import qk_dot_fp8

NEG_INF = -1e30
DEFAULT_BK = 256


def _qk(q, k, *, fp8: bool, narrow_dot: bool):
    """The paged kernel's QK^T contraction: f32 dot, or the per-row fp8
    tile path (``common.qk_dot_fp8``) behind ``fp8``."""
    if fp8:
        return qk_dot_fp8(q, k, narrow_dot=narrow_dot)
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, window: int,
                   bk: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, D)
    pos = pos_ref[0]                                  # (bk,) int32
    q_pos = qpos_ref[0]                               # scalar int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = (pos >= 0) & (pos <= q_pos)
    if window > 0:
        ok &= (q_pos - pos) < window
    s = jnp.where(ok[None, :], s, NEG_INF)            # (G, bk)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(tab_ref, start_ref, ntok_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, window: int, bs: int, n_b: int, T: int,
                  G: int, quantized: bool, fp8: bool, narrow_dot: bool):
    """One (slot, logical block) grid step over ALL KV heads.  The K/V
    tiles are whole pool blocks ``(bs, KV, D)`` — their last two dims are
    the pool's own, which is what Mosaic's tiling rule needs when KV is
    not a multiple of 8 — and the heads are swept inside the body.  Query
    rows are (T, G) flattened to T*G per head: row r is token t = r // G
    at absolute position ``start + t``; tokens beyond ``n_tok`` are
    padding (fully masked).  Quantized pools carry (bs, KV) f32 scale
    tiles on the same block-table index map and dequantize on load."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    s_idx = pl.program_id(0)
    ib = pl.program_id(1)
    KV = k_ref.shape[2]

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[s_idx]                          # scalar int32
    n_tok = ntok_ref[s_idx]                           # scalar int32
    mapped = tab_ref[s_idx, ib] >= 0                  # −1 = unmapped block
    row_t = jax.lax.broadcasted_iota(jnp.int32, (T * G, 1), 0) // G
    q_pos = start + row_t                             # (T*G, 1)
    valid = (start >= 0) & (row_t < n_tok)
    # blocks hold contiguous positions: logical position = ib*bs + lane
    k_pos = ib * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    ok = valid & mapped & (k_pos <= q_pos)
    if window > 0:
        ok &= (q_pos - k_pos) < window
    if quantized:
        head = jax.lax.broadcasted_iota(jnp.int32, (bs, KV), 1)
        ks_all = ks_ref[0].astype(jnp.float32)        # (bs, KV)
        vs_all = vs_ref[0].astype(jnp.float32)

    for h in range(KV):
        q = q_ref[0, h].astype(jnp.float32)           # (T*G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)     # (bs, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quantized:   # select head h's scale column: (bs, 1)
            k = k * jnp.sum(jnp.where(head == h, ks_all, 0.0), axis=1,
                            keepdims=True)
            v = v * jnp.sum(jnp.where(head == h, vs_all, 0.0), axis=1,
                            keepdims=True)
        s = _qk(q, k, fp8=fp8, narrow_dot=narrow_dot) * scale
        s = jnp.where(ok, s, NEG_INF)                 # (T*G, bs)
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(ib == n_b - 1)
    def _fin():
        for h in range(KV):
            o_ref[0, h] = (acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
                           ).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, scales, block_tables, start_pos, n_tokens,
                *, window: int, interpret: bool, fp8: bool):
    """Shared launcher: grid (S, MB), the block table (and per-slot start /
    count) as scalar-prefetch operands so each step DMAs the physical block
    the table names.  q (S, T, KV, G, D) is laid out head-major as
    (S, KV, T*G, D) for the kernel and back again on the way out."""
    S, T, KV, G, D = q.shape
    NB, bs = k_pool.shape[:2]
    MB = block_tables.shape[1]
    qh = q.transpose(0, 2, 1, 3, 4).reshape(S, KV, T * G, D)
    kernel = functools.partial(
        _paged_kernel, scale=1.0 / math.sqrt(D), window=window, bs=bs,
        n_b=MB, T=T, G=G, quantized=bool(scales), fp8=fp8,
        narrow_dot=fp8 and not interpret)

    def slot_map(s, ib, tab, st, nt):
        return (s, 0, 0, 0)

    def block_map(s, ib, tab, st, nt):
        return (jnp.maximum(tab[s, ib], 0), 0, 0, 0)

    def scale_map(s, ib, tab, st, nt):
        return (jnp.maximum(tab[s, ib], 0), 0, 0)

    in_specs = [pl.BlockSpec((1, KV, T * G, D), slot_map),
                pl.BlockSpec((1, bs, KV, D), block_map),
                pl.BlockSpec((1, bs, KV, D), block_map)]
    in_specs += [pl.BlockSpec((1, bs, KV), scale_map)] * len(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, T * G, D), slot_map),
        scratch_shapes=[
            pltpu.VMEM((KV, T * G, 1), jnp.float32),
            pltpu.VMEM((KV, T * G, 1), jnp.float32),
            pltpu.VMEM((KV, T * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, T * G, D), q.dtype),
        interpret=interpret,
    )(block_tables, start_pos, n_tokens, qh, k_pool, v_pool, *scales)
    return out.reshape(S, KV, T, G, D).transpose(0, 2, 1, 3, 4)


def paged_verify_attention_fwd(q, k_pool, v_pool, block_tables, start_pos,
                               n_tokens, *, window: int = 0,
                               interpret: bool = True, fp8: bool = False):
    """Multi-query block-table-indexed decode attention (speculative
    verification): each slot attends with T query tokens at contiguous
    positions ``start_pos[s] + t`` (t < ``n_tokens[s]``; the rest are
    padding whose rows come back garbage the caller must ignore).

    q: (S, T, KV, G, D); k_pool/v_pool: (NB, bs, KV, D); block_tables:
    (S, MB) int32 (−1 = unmapped); start_pos: (S,) int32 (−1 = inactive
    slot); n_tokens: (S,) int32 live query tokens per slot.  The fresh K/V
    for all T tokens must already be scattered into the pool — causality
    among them is purely positional, exactly like the single-query kernel.
    ``fp8`` runs QK^T on per-row fp8 tiles.  Returns (S, T, KV, G, D)."""
    return _paged_call(q, k_pool, v_pool, (), block_tables, start_pos,
                       n_tokens, window=window, interpret=interpret, fp8=fp8)


def paged_verify_attention_dequant_fwd(q, k_pool, v_pool, k_scale, v_scale,
                                       block_tables, start_pos, n_tokens, *,
                                       window: int = 0,
                                       interpret: bool = True):
    """Quantized-pool multi-query paged decode attention: ``k_pool`` /
    ``v_pool`` hold the narrow payload (int8 / fp8) and ``k_scale`` /
    ``v_scale`` the (NB, bs, KV) f32 per-token-per-head amax scales;
    tiles are dequantized on load inside the kernel.  Shapes otherwise
    as ``paged_verify_attention_fwd``."""
    return _paged_call(q, k_pool, v_pool, (k_scale, v_scale), block_tables,
                       start_pos, n_tokens, window=window,
                       interpret=interpret, fp8=False)


def _one_token(q_pos):
    return jnp.where(q_pos >= 0, 1, 0).astype(jnp.int32)


def paged_decode_attention_dequant_fwd(q, k_pool, v_pool, k_scale, v_scale,
                                       block_tables, q_pos, *,
                                       window: int = 0,
                                       interpret: bool = True):
    """Quantized-pool single-token paged decode attention (see
    ``paged_verify_attention_dequant_fwd`` for the scale contract).
    Shapes otherwise as ``paged_decode_attention_fwd``."""
    return paged_verify_attention_dequant_fwd(
        q[:, None], k_pool, v_pool, k_scale, v_scale, block_tables, q_pos,
        _one_token(q_pos), window=window, interpret=interpret)[:, 0]


def paged_decode_attention_fwd(q, k_pool, v_pool, block_tables, q_pos, *,
                               window: int = 0, interpret: bool = True,
                               fp8: bool = False):
    """Block-table-indexed decode attention over a shared paged KV pool —
    the T = 1 case of ``paged_verify_attention_fwd``.

    q: (S, KV, G, D) one token per active slot; k_pool/v_pool: (NB, bs, KV, D)
    fixed-size physical blocks; block_tables: (S, MB) int32 — logical block j
    of slot s lives in physical block ``block_tables[s, j]`` (−1 = unmapped);
    q_pos: (S,) int32 absolute query positions (−1 = inactive slot).

    The gather never materializes a per-slot contiguous cache.  Validity is
    positional (blocks hold contiguous positions), so stale pool contents
    beyond ``q_pos`` and unmapped table slots are masked, never read into the
    softmax.  Returns (S, KV, G, D)."""
    return paged_verify_attention_fwd(
        q[:, None], k_pool, v_pool, block_tables, q_pos, _one_token(q_pos),
        window=window, interpret=interpret, fp8=fp8)[:, 0]


def decode_attention_fwd(q, k, v, pos, q_pos, *, window: int = 0,
                         bk: int = DEFAULT_BK, interpret: bool = True):
    """q: (B, KV, G, D) one token per request, grouped query heads;
    k/v: (B, KV, S, D) cache; pos: (B, S) int32 slot positions (−1 empty);
    q_pos: (B,) int32 absolute query positions.  Returns (B, KV, G, D)."""
    B, KV, G, D = q.shape
    S = k.shape[2]
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    n_k = S // bk
    scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               bk=bk, n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(B, KV, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, ik: (b,)),            # q_pos
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, bk), lambda b, h, ik: (b, ik)),       # pos
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, q, k, v, pos)
