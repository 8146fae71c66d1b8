"""Jitted public wrapper around the flash attention Pallas kernels, with
the backward wired in through ``jax.custom_vjp``.

``interpret`` defaults to *backend-selected* via ``repro.kernels.common``:
the kernel bodies run under the Pallas interpreter on CPU hosts (same
arithmetic, Python-speed — what the correctness sweeps use) and compile
through Mosaic on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, static):
    return flash_attention_fwd(q, k, v, **dict(static))[0]


def _attend_fwd(q, k, v, static):
    o, lse = flash_attention_fwd(q, k, v, **dict(static))
    return o, (q, k, v, o, lse)


def _attend_bwd(static, res, do):
    q, k, v, o, lse = res
    B, H, Sq, _ = q.shape
    KV, Sk, D = k.shape[1:]
    # di = rowsum(dO ∘ O): the softmax backward's per-row correction
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]
    dq, dk, dv = flash_attention_bwd(q, k, v, do, lse, di, **dict(static))
    # per-query-head dK/dV -> sum each GQA group onto its KV head
    dk = dk.reshape(B, KV, H // KV, Sk, D).sum(axis=2)
    dv = dv.reshape(B, KV, H // KV, Sk, D).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "fp8",
                                             "dot_dtype"))
def _flash_attention(q, k, v, *, causal, window, bq, bk, interpret, fp8,
                     dot_dtype):
    static = (("causal", causal), ("window", window), ("bq", bq),
              ("bk", bk), ("interpret", interpret), ("dot_dtype", dot_dtype))
    if fp8:         # forward only: the fp8 QK^T has no backward
        return flash_attention_fwd(q, k, v, fp8=True, **dict(static))[0]
    return _attend(q, k, v, static)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None,
                    fp8: bool = False, dot_dtype=jnp.bfloat16):
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) grouped-query.  Differentiable
    (a fused backward, see kernel.py) unless ``fp8``, which runs the QK^T
    contraction on per-row fp8 tiles.  ``dot_dtype`` is the operand dtype
    of every matmul in the kernels (bfloat16: one MXU pass, float32
    accumulation)."""
    interpret = resolve_interpret(interpret)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            bq=bq, bk=bk, interpret=interpret, fp8=fp8,
                            dot_dtype=jnp.dtype(dot_dtype))
