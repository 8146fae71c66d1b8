"""Jitted public wrapper around the flash attention Pallas kernel.

``interpret`` defaults to *backend-selected* via ``repro.kernels.common``:
the kernel body runs under the Pallas interpreter on CPU hosts (same
arithmetic, Python-speed — what the correctness sweeps use) and compiles
through Mosaic on TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.common import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "fp8"))
def _flash_attention(q, k, v, *, causal, window, bq, bk, interpret, fp8):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret, fp8=fp8)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = 128,
                    bk: int = 128, interpret: Optional[bool] = None,
                    fp8: bool = False):
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) grouped-query.  ``fp8`` runs
    the QK^T contraction on per-row fp8 tiles (see kernel.py)."""
    interpret = resolve_interpret(interpret)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            bq=bq, bk=bk, interpret=interpret, fp8=fp8)
