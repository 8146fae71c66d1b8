"""Block-wise flash attention, forward and backward — Pallas TPU kernels.

TPU-native adaptation of the FlashAttention recurrence, three
``pallas_call``s over the same (BQ, BK) tiling:

* **forward** — grid = (batch, q_heads, Sq/BQ, Sk/BK), minor-most axis the
  KV block, so the online-softmax state (m, l, acc) lives in VMEM scratch
  across the sequential KV sweep and never touches HBM.  Writes O and the
  per-row log-sum-exp ``lse = m + log l`` (B, H, 1, Sq) float32, the
  residual the backward needs;
* **dK/dV sweep** — grid = (batch, q_heads, Sk/BK, Sq/BQ): one KV block's
  dK and dV accumulate in VMEM over the Q blocks.  It works in the
  transposed orientation (Pᵀ = exp(K·Qᵀ·scale - lse)), so lse and
  ``D = rowsum(dO ∘ O)`` broadcast as lane-dense rows and every matmul is
  a plain or RHS-transposed contraction;
* **dQ sweep** — grid = (batch, q_heads, Sq/BQ, Sk/BK): dQ accumulates over
  the KV blocks of one Q block.

Both backward sweeps recompute P one tile at a time from Q, K and lse: no
(Sq, Sk) array is ever written to HBM.

* GQA without materialising repeated KV: the K/V BlockSpec index_map sends
  query head ``h`` to KV head ``h // group``.  The dK/dV sweep writes one
  gradient per *query* head; the wrapper sums each group.
* Causal + sliding-window masking via block-local iota against absolute
  positions (query i and key j sit at positions i and j).  Blocks with no
  visible pair are skipped with ``pl.when``, and their index_map is
  clamped onto the nearest relevant block, so a skipped step starts no
  DMA: causal at S=2048 with 128-wide blocks does 136 of 256 blocks.  Only
  blocks that straddle the diagonal or the window edge build a mask.
* Precision: every ``dot_general`` takes ``dot_dtype`` operands (bfloat16
  by default: the Q, K, V, dO and P/dS tiles are cast in the kernel body)
  with ``preferred_element_type=float32`` — one MXU pass, as XLA's default
  precision gives a float32 einsum on a TPU.  Softmax statistics,
  accumulators, lse and the outputs O, dQ, dK, dV stay float32 (O in the
  input dtype).  ``dot_dtype=float32`` keeps the tiles in float32.
* Block sizes are the caller's (128 by default); head_dim is taken whole
  and is not padded (64 and 128 are the widths in use).

Validated on CPU with ``interpret=True`` against ``ref.reference_attention``
and its ``jax.grad``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import qk_dot_fp8

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))       # A·Bᵀ: contract both minor dims
_NN = (((1,), (0,)), ((), ()))       # A·B


def _dot(a, b, dims, dot_dtype):
    # A bfloat16 product is exact in one pass whatever precision the caller
    # asked for (Mosaic refuses HIGHEST on bfloat16 operands); float32
    # operands keep the ambient precision.
    precision = (None if dot_dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a.astype(dot_dtype), b.astype(dot_dtype), dims,
                               precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Block geometry: which (q block, kv block) pairs see each other
# ---------------------------------------------------------------------------

def _relevant(q_start, k_start, *, bq, bk, causal, window):
    """Some (query, key) pair of the block pair is visible."""
    ok = jnp.asarray(True)
    if causal:
        ok = jnp.logical_and(ok, k_start <= q_start + bq - 1)
    if window is not None:
        # newest key in the block within the window of the oldest query
        ok = jnp.logical_and(ok, k_start + bk - 1 >= q_start - window + 1)
    return ok


def _needs_mask(q_start, k_start, *, bq, bk, causal, window):
    """Some pair of the block pair is hidden (the block straddles an edge)."""
    hidden = jnp.asarray(False)
    if causal:
        hidden = jnp.logical_or(hidden, k_start + bk - 1 > q_start)
    if window is not None:
        hidden = jnp.logical_or(hidden,
                                q_start + bq - 1 - k_start >= window)
    return hidden


def _mask(s, q_start, k_start, *, q_axis, causal, window):
    """The score tile ``s`` with hidden (query, key) pairs at NEG_INF;
    queries run along axis ``q_axis`` of the tile."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    ok = jnp.ones(s.shape, jnp.bool_)
    if causal:
        ok = jnp.logical_and(ok, k_pos <= q_pos)
    if window is not None:
        ok = jnp.logical_and(ok, q_pos - k_pos < window)
    return jnp.where(ok, s, NEG_INF)


def _kv_range(iq, *, bq, bk, n_k, causal, window):
    """First and last KV block relevant to Q block ``iq`` (traced ints)."""
    first = jnp.int32(0)
    last = jnp.int32(n_k - 1)
    if causal:
        last = jnp.minimum(last, (iq * bq + bq - 1) // bk)
    if window is not None:
        first = jnp.maximum(first, (iq * bq - window + 1) // bk)
    return first, last


def _q_range(ik, *, bq, bk, n_q, causal, window):
    """First and last Q block relevant to KV block ``ik`` (traced ints)."""
    first = jnp.int32(0)
    last = jnp.int32(n_q - 1)
    if causal:
        first = jnp.maximum(first, (ik * bk) // bq)
    if window is not None:
        last = jnp.minimum(last, (ik * bk + bk - 1 + window - 1) // bq)
    return first, last


def _branches(body, q_start, k_start, **geo):
    """Run ``body(masked)`` on the relevant block pair, building the mask
    only where the pair straddles an edge."""
    relevant = _relevant(q_start, k_start, **geo)
    needs_mask = _needs_mask(q_start, k_start, **geo)

    @pl.when(jnp.logical_and(relevant, needs_mask))
    def _masked():
        body(True)

    @pl.when(jnp.logical_and(relevant, jnp.logical_not(needs_mask)))
    def _full():
        body(False)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, window: Optional[int],
                bq: int, bk: int, n_k_blocks: int, dot_dtype,
                fp8: bool = False, narrow_dot: bool = False):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    q_start = iq * bq
    k_start = ik * bk

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked: bool):
        q = q_ref[0, 0]                                 # (bq, d)
        k = k_ref[0, 0]                                 # (bk, d)
        if fp8:     # per-row fp8 tiles; narrow MXU contraction on TPU
            s = qk_dot_fp8(q.astype(jnp.float32), k.astype(jnp.float32),
                           narrow_dot=narrow_dot)
        else:
            s = _dot(q, k, _NT, dot_dtype)              # (bq, bk)
        s = s * scale
        if masked:
            s = _mask(s, q_start, k_start, q_axis=0, causal=causal,
                      window=window)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p, v_ref[0, 0], _NN,
                                                   dot_dtype)
        m_scr[...] = m_new

    _branches(body, q_start, k_start, bq=bq, bk=bk, causal=causal,
              window=window)

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                   # (bq, 1)
        lse_ref[0, 0] = jnp.transpose(
            jnp.broadcast_to(lse, (bq, 128)))[:1]       # (1, bq) row


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                        interpret: bool = True, fp8: bool = False,
                        dot_dtype=jnp.bfloat16):
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) with H % KV == 0.
    Returns (O (B, H, Sq, D) in q's dtype, lse (B, H, 1, Sq) float32).

    ``fp8=True`` runs the QK^T contraction on per-row fp8_e4m3 tiles with
    per-tile amax scales (``common.qk_dot_fp8``) — the narrow-dtype MXU
    dot only when compiling (interpret mode keeps the quantization but
    contracts in f32, since the interpreter has no fp8 matmul units).
    The PV matmul takes ``dot_dtype``: P is a softmax output in [0, 1]
    whose dynamic range fp8 would waste."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_q, n_k = Sq // bq, Sk // bk
    geo = dict(bq=bq, bk=bk, causal=causal, window=window)

    def kv_map(b, h, iq, ik):
        first, last = _kv_range(iq, n_k=n_k, **geo)
        return (b, h // group, jnp.clip(ik, first, last), 0)

    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(D), n_k_blocks=n_k,
        dot_dtype=dot_dtype, fp8=fp8, narrow_dot=fp8 and not interpret,
        **geo)
    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                 dk_ref, dv_ref, *, scale: float, causal: bool,
                 window: Optional[int], bq: int, bk: int, dot_dtype):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    q_start = iq * bq
    k_start = ik * bk

    @pl.when(iq == 0)          # dk/dv blocks stay in VMEM across iq
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def body(masked: bool):
        q = q_ref[0, 0]                                 # (bq, d)
        do = do_ref[0, 0]                               # (bq, d)
        k = k_ref[0, 0]                                 # (bk, d)
        s_t = _dot(k, q, _NT, dot_dtype) * scale        # (bk, bq) = S^T
        if masked:
            s_t = _mask(s_t, q_start, k_start, q_axis=1, causal=causal,
                        window=window)
        p_t = jnp.exp(s_t - lse_ref[0, 0])              # lse row (1, bq)
        dv_ref[0, 0] += _dot(p_t, do, _NN, dot_dtype)
        dp_t = _dot(v_ref[0, 0], do, _NT, dot_dtype)    # (bk, bq) = dP^T
        ds_t = p_t * (dp_t - di_ref[0, 0]) * scale
        dk_ref[0, 0] += _dot(ds_t, q, _NN, dot_dtype)

    _branches(body, q_start, k_start, bq=bq, bk=bk, causal=causal,
              window=window)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, *,
               scale: float, causal: bool, window: Optional[int], bq: int,
               bk: int, dot_dtype):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    q_start = iq * bq
    k_start = ik * bk

    @pl.when(ik == 0)          # the dq block stays in VMEM across ik
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def body(masked: bool):
        k = k_ref[0, 0]                                 # (bk, d)
        s = _dot(q_ref[0, 0], k, _NT, dot_dtype) * scale    # (bq, bk)
        if masked:
            s = _mask(s, q_start, k_start, q_axis=0, causal=causal,
                      window=window)
        lse = lse_ref[0, 0, 0][:, None]                 # (bq, 1) column
        di = di_ref[0, 0, 0][:, None]
        p = jnp.exp(s - lse)
        dp = _dot(do_ref[0, 0], v_ref[0, 0], _NT, dot_dtype)
        ds = p * (dp - di) * scale
        dq_ref[0, 0] += _dot(ds, k, _NN, dot_dtype)

    _branches(body, q_start, k_start, bq=bq, bk=bk, causal=causal,
              window=window)


def flash_attention_bwd(q, k, v, do, lse, di, *, causal: bool = True,
                        window: Optional[int] = None, bq: int = DEFAULT_BQ,
                        bk: int = DEFAULT_BK, interpret: bool = True,
                        dot_dtype=jnp.bfloat16):
    """Gradients of ``flash_attention_fwd``'s O.

    q, do: (B, H, Sq, D); k, v: (B, KV, Sk, D); lse (from the forward) and
    ``di = rowsum(dO ∘ O)``: (B, H, 1, Sq) float32.  Returns float32
    dq (B, H, Sq, D) and dk, dv per *query* head (B, H, Sk, D)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_q, n_k = Sq // bq, Sk // bk
    geo = dict(bq=bq, bk=bk, causal=causal, window=window)
    scale = 1.0 / math.sqrt(D)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

    # dK/dV: KV block outer, Q block inner (clamped onto relevant blocks)
    def q_map(b, h, ik, iq):
        first, last = _q_range(ik, n_q=n_q, **geo)
        return (b, h, jnp.clip(iq, first, last), 0)

    def row_map(b, h, ik, iq):
        first, last = _q_range(ik, n_q=n_q, **geo)
        return (b, h, 0, jnp.clip(iq, first, last))

    kv_fixed = lambda b, h, ik, iq: (b, h // group, ik, 0)
    out_fixed = lambda b, h, ik, iq: (b, h, ik, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, dot_dtype=dot_dtype,
                          **geo),
        grid=(B, H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map),          # q
            pl.BlockSpec((1, 1, bk, D), kv_fixed),       # k
            pl.BlockSpec((1, 1, bk, D), kv_fixed),       # v
            pl.BlockSpec((1, 1, bq, D), q_map),          # do
            pl.BlockSpec((1, 1, 1, bq), row_map),        # lse
            pl.BlockSpec((1, 1, 1, bq), row_map),        # di
        ],
        out_specs=[pl.BlockSpec((1, 1, bk, D), out_fixed),
                   pl.BlockSpec((1, 1, bk, D), out_fixed)],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32)] * 2,
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, di)

    # dQ: Q block outer, KV block inner
    def kv_map(b, h, iq, ik):
        first, last = _kv_range(iq, n_k=n_k, **geo)
        return (b, h // group, jnp.clip(ik, first, last), 0)

    q_fixed = lambda b, h, iq, ik: (b, h, iq, 0)
    row_fixed = lambda b, h, iq, ik: (b, h, 0, iq)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, dot_dtype=dot_dtype,
                          **geo),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_fixed),        # q
            pl.BlockSpec((1, 1, bk, D), kv_map),         # k
            pl.BlockSpec((1, 1, bk, D), kv_map),         # v
            pl.BlockSpec((1, 1, bq, D), q_fixed),        # do
            pl.BlockSpec((1, 1, 1, bq), row_fixed),      # lse
            pl.BlockSpec((1, 1, 1, bq), row_fixed),      # di
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), q_fixed),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, di)
    return dq, dk, dv
