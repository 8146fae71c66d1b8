"""Jitted wrappers for the quantize kernels: arbitrary leaf shapes in,
flattened zero-padded ``(K, R, LANE)`` row views inside.

Public surface (all parameterized over ``dtype`` in ``kernel.QDTYPES``):

* ``quantize_ef(x, residual, dtype=)`` — quantize + error-feedback
  residual with one scale per worker row (per-tensor-per-worker);
* ``dequantize(q, scale)`` — the inverse.

Degenerate leaves are handled here, NOT in the kernels: scalar (0-d)
params run through a (1, 1) view and 0-size sentinel leaves skip the
kernel entirely (both mirror the ``ref`` oracles bit-for-bit), so codecs
can map over any parameter pytree.

``interpret`` defaults to backend-selected via ``repro.kernels.common``:
interpreted on a CPU backend, compiled everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import (default_interpret, pallas_mode,
                                  resolve_interpret)
from repro.kernels.quantize.kernel import (LANE, block_rows, dequantize_fwd,
                                           quantize_ef_fwd)

__all__ = ["quantize_ef", "dequantize", "default_interpret", "pallas_mode"]


def _rows(x) -> Tuple[jax.Array, int]:
    """(K, ...) -> zero-padded (K, R, LANE) row view, R a multiple of
    ``block_rows(R)``; also returns the unpadded per-row element count."""
    k = x.shape[0]
    flat = x.reshape(k, -1)
    m = flat.shape[1]
    r = -(-m // LANE)
    r = -(-r // block_rows(r)) * block_rows(r)
    if r * LANE != m:
        flat = jnp.pad(flat, ((0, 0), (0, r * LANE - m)))
    return flat.reshape(k, r, LANE), m


def _unrows(y, m: int, shape):
    return y.reshape(shape[0], -1)[:, :m].reshape(shape)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _quantize_ef(x, residual, *, dtype: str, interpret: bool):
    xr, m = _rows(x.astype(jnp.float32))
    rr = (jnp.zeros_like(xr) if residual is None
          else _rows(residual.astype(jnp.float32))[0])
    q, nr, s = quantize_ef_fwd(xr, rr, dtype=dtype, interpret=interpret)
    return (_unrows(q, m, x.shape), _unrows(nr, m, x.shape),
            s.reshape((x.shape[0],) + (1,) * (x.ndim - 1)))


def quantize_ef(x, residual=None, *, dtype: str = "int8",
                interpret: Optional[bool] = None):
    """Per-worker-row symmetric quantize + residual update.

    ``x``: (K, ...) delta; ``residual``: matching error-feedback carry (or
    None for plain quantization); ``dtype``: int8 / fp8_e4m3 / fp8_e5m2.
    Returns ``(q, new_residual, scale)`` shaped like the jnp oracle
    (``ref.reference_quantize_ef``), with which it agrees bit for bit.
    """
    interpret = resolve_interpret(interpret)
    if x.ndim == 0:                      # scalar param: quantize elementwise
        q, nr, s = _quantize_ef(
            x.reshape(1, 1),
            None if residual is None else residual.reshape(1, 1),
            dtype=dtype, interpret=interpret)
        return q.reshape(()), nr.reshape(()), s.reshape(())
    if x.size == 0:                      # 0-size sentinel leaf: no kernel
        from repro.kernels.quantize.ref import reference_quantize_ef
        return reference_quantize_ef(x, residual, dtype=dtype)
    return _quantize_ef(x, residual, dtype=dtype, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dequantize(q, scale, *, interpret: bool):
    qr, m = _rows(q)
    out = dequantize_fwd(qr, scale.reshape(q.shape[0]), interpret=interpret)
    return _unrows(out, m, q.shape)


def dequantize(q, scale, *, interpret: Optional[bool] = None):
    """Narrow (K, ...) payload x per-row scales (K, 1, ..., 1) -> f32."""
    interpret = resolve_interpret(interpret)
    if q.ndim == 0:
        return _dequantize(q.reshape(1, 1), scale.reshape(1, 1),
                           interpret=interpret).reshape(())
    if q.size == 0:
        return q.astype(jnp.float32)
    return _dequantize(q, scale, interpret=interpret)
