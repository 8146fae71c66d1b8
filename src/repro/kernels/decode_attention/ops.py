"""Jitted wrappers for the decode attention Pallas kernels.

``interpret`` defaults to *backend-selected* via
``repro.kernels.common``: the Pallas interpreter runs only on a CPU
backend (where Mosaic cannot compile); elsewhere the kernels compile, and
``pallas_mode()`` reports the resolved mode so reports can record which
path actually ran.  (``default_interpret``/``pallas_mode`` are
re-exported here for backward compatibility — ``repro.kernels.common``
is the canonical home.)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.common import (default_interpret, pallas_mode,
                                  resolve_interpret)
from repro.kernels.decode_attention.kernel import (
    decode_attention_fwd, paged_decode_attention_dequant_fwd,
    paged_decode_attention_fwd, paged_verify_attention_dequant_fwd,
    paged_verify_attention_fwd)

__all__ = ["decode_attention", "paged_decode_attention",
           "paged_decode_attention_dequant", "paged_verify_attention",
           "paged_verify_attention_dequant", "default_interpret",
           "pallas_mode"]


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def _decode_attention(q, k, v, pos, q_pos, *, window, bk, interpret):
    return decode_attention_fwd(q, k, v, pos, q_pos, window=window, bk=bk,
                                interpret=interpret)


def decode_attention(q, k, v, pos, q_pos, *, window: int = 0, bk: int = 256,
                     interpret: Optional[bool] = None):
    interpret = resolve_interpret(interpret)
    return _decode_attention(q, k, v, pos, q_pos, window=window, bk=bk,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "fp8"))
def _paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                            window, interpret, fp8):
    return paged_decode_attention_fwd(q, k_pool, v_pool, block_tables, q_pos,
                                      window=window, interpret=interpret,
                                      fp8=fp8)


def paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos, *,
                           window: int = 0,
                           interpret: Optional[bool] = None,
                           fp8: bool = False):
    """Block-table-indexed decode attention (see kernel.py for shapes).
    ``fp8`` runs QK^T on per-row fp8 tiles (``ModelConfig.fp8_matmul``)."""
    interpret = resolve_interpret(interpret)
    return _paged_decode_attention(q, k_pool, v_pool, block_tables, q_pos,
                                   window=window, interpret=interpret,
                                   fp8=fp8)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_decode_attention_dequant(q, k_pool, v_pool, k_scale, v_scale,
                                    block_tables, q_pos, *, window,
                                    interpret):
    return paged_decode_attention_dequant_fwd(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, q_pos,
        window=window, interpret=interpret)


def paged_decode_attention_dequant(q, k_pool, v_pool, k_scale, v_scale,
                                   block_tables, q_pos, *, window: int = 0,
                                   interpret: Optional[bool] = None):
    """Quantized-pool paged decode attention: narrow K/V payload plus
    (NB, bs, KV) f32 scales, dequantized on load (see kernel.py)."""
    interpret = resolve_interpret(interpret)
    return _paged_decode_attention_dequant(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, q_pos,
        window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_verify_attention_dequant(q, k_pool, v_pool, k_scale, v_scale,
                                    block_tables, start_pos, n_tokens, *,
                                    window, interpret):
    return paged_verify_attention_dequant_fwd(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, start_pos,
        n_tokens, window=window, interpret=interpret)


def paged_verify_attention_dequant(q, k_pool, v_pool, k_scale, v_scale,
                                   block_tables, start_pos, n_tokens, *,
                                   window: int = 0,
                                   interpret: Optional[bool] = None):
    """Quantized-pool multi-query paged decode attention — the speculative-
    verification variant with dequant-on-load (see kernel.py)."""
    interpret = resolve_interpret(interpret)
    return _paged_verify_attention_dequant(
        q, k_pool, v_pool, k_scale, v_scale, block_tables, start_pos,
        n_tokens, window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "fp8"))
def _paged_verify_attention(q, k_pool, v_pool, block_tables, start_pos,
                            n_tokens, *, window, interpret, fp8):
    return paged_verify_attention_fwd(q, k_pool, v_pool, block_tables,
                                      start_pos, n_tokens, window=window,
                                      interpret=interpret, fp8=fp8)


def paged_verify_attention(q, k_pool, v_pool, block_tables, start_pos,
                           n_tokens, *, window: int = 0,
                           interpret: Optional[bool] = None,
                           fp8: bool = False):
    """Multi-query-per-slot paged decode attention — the speculative-
    verification variant (see kernel.py for shapes).  ``fp8`` runs QK^T
    on per-row fp8 tiles (``ModelConfig.fp8_matmul``)."""
    interpret = resolve_interpret(interpret)
    return _paged_verify_attention(q, k_pool, v_pool, block_tables,
                                   start_pos, n_tokens, window=window,
                                   interpret=interpret, fp8=fp8)
