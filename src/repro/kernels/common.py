"""Shared interpret-mode resolution for every Pallas kernel package,
plus the fp8 per-tile QK^T contraction the attention kernels share.

One selection point for the whole kernel suite: ``interpret`` defaults to
*backend-selected* — the Pallas interpreter runs if and only if the
backend is ``cpu`` (where Mosaic cannot compile); everywhere else the
kernels compile.  There is no override: a kernel on the chip either
compiles or fails, it never slows down to the interpreter.
``pallas_mode()`` reports the resolved mode so reports can record which
path actually ran.

Every ``kernels/<name>/ops.py`` must resolve ``interpret`` through this
module (enforced by the ``kernel-contract`` lint pass in
``repro.tools.lint``) instead of keeping a private copy or hardcoding a
default — a hardcoded ``interpret=True`` silently runs the Python-speed
interpreter on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["default_interpret", "pallas_mode", "resolve_interpret",
           "qk_dot_fp8", "FP8_QMAX"]

FP8_QMAX = 448.0        # float8_e4m3fn saturation (matches quantize.QMAX)


def qk_dot_fp8(q, k, *, narrow_dot: bool):
    """fp8 per-tile QK^T for attention kernel bodies: quantize each ROW of
    the f32 ``q`` (rows, D) and ``k`` (cols, D) tiles to fp8_e4m3 with its
    own amax scale, contract over D, and rescale by the outer product of
    the row scales (scales factor out of the dot exactly).

    ``narrow_dot=True`` feeds the narrow tiles straight to the MXU
    (``preferred_element_type=f32`` accumulate) — the TPU fast path;
    ``narrow_dot=False`` (CPU / Pallas interpreter, where fp8 matmul units
    don't exist) upcasts the already-quantized tiles and contracts in f32:
    identical quantization numerics, full-precision multiply.  Returns
    (rows, cols) f32 scores.
    """
    dims = (((1,), (1,)), ((), ()))
    qs = jnp.maximum(jnp.max(jnp.abs(q), axis=1, keepdims=True),
                     1e-12) / FP8_QMAX
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=1, keepdims=True),
                     1e-12) / FP8_QMAX
    q8 = jnp.clip(q / qs, -FP8_QMAX, FP8_QMAX).astype(jnp.float8_e4m3fn)
    k8 = jnp.clip(k / ks, -FP8_QMAX, FP8_QMAX).astype(jnp.float8_e4m3fn)
    if not narrow_dot:
        q8, k8 = q8.astype(jnp.float32), k8.astype(jnp.float32)
    s = jax.lax.dot_general(q8, k8, dims,
                            preferred_element_type=jnp.float32)
    return s * qs * ks[:, 0][None, :]


def default_interpret() -> bool:
    """Interpret if and only if the backend is the CPU."""
    return jax.default_backend() == "cpu"


def pallas_mode() -> str:
    """'interpret' or 'compiled' — what the kernels will actually run as."""
    return "interpret" if default_interpret() else "compiled"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> the backend-selected default; explicit bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)
