"""Fused symmetric quantization kernels for the outer-sync transport.

One parameterized kernel pair backs every quantized wire in the repo
(``Int8Symmetric`` / ``Fp8Codec`` in ``repro.core.transport``):

* ``quantize_ef_fwd`` — quantize + error-feedback residual update with one
  scale per worker row.  A per-row scale needs the row's global amax
  before any element can be quantized, so it runs in two passes: an XLA
  reduction of ``|x + residual|`` per row (fused, reads both operands
  once, never materializes ``e``), then a tiled Pallas pass that forms
  ``e = x + residual`` in VMEM and writes the narrow payload AND the new
  residual ``e - q*scale`` together.
* ``dequantize_fwd`` — narrow payload × per-row scale -> f32.

Both kernels work on a ``(K, R, LANE)`` row view of the flattened leaf and
sweep ``(1, rows, LANE)`` blocks, so every block fills whole (sublane,
lane) tiles whatever K and the leaf size are, and VMEM use per step is
bounded by ``ROWS`` (a whole-leaf block of a d20 MLP matrix would need
over 400 MiB).

Supported target dtypes (``QMAX`` is the symmetric clip bound; scale =
max(amax, eps) / QMAX):

    dtype      QMAX     payload
    int8       127      round+clip
    fp8_e4m3   448      clip+RNE cast
    fp8_e5m2   57344    clip+RNE cast

fp8 targets clip to ±QMAX *before* the cast: e4m3fn has no inf encoding,
so an unclipped overflow would become NaN on the wire.  The arithmetic is
the oracle's (``ref.reference_quantize_ef``) op for op, so the results are
bit-identical to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128          # TPU lane width: row views are (K, R, LANE)
ROWS = 512          # sublane rows per grid step (256 KiB of f32 per block)
SCALE_EPS = 1e-12   # matches the jnp oracle: scale = max(amax, eps) / QMAX

# symmetric clip bound per target dtype (the finfo/iinfo max of each)
QMAX = {"int8": 127.0, "fp8_e4m3": 448.0, "fp8_e5m2": 57344.0}
QDTYPES = ("int8", "fp8_e4m3", "fp8_e5m2")


def target_dtype(dtype: str):
    """jnp dtype for a quantize target name (raises on unknown names)."""
    if dtype == "int8":
        return jnp.int8
    if dtype == "fp8_e4m3":
        return jnp.float8_e4m3fn
    if dtype == "fp8_e5m2":
        return jnp.float8_e5m2
    raise ValueError(f"unknown quantize target {dtype!r}; "
                     f"expected one of {QDTYPES}")


def block_rows(r: int) -> int:
    """Rows per grid step for an R-row view: the whole view when it is
    small (a block dim equal to the array dim is always legal), else
    ``ROWS`` — callers pad R to a multiple of it."""
    return r if r <= ROWS else ROWS


def _quantize_ef_kernel(s_ref, x_ref, r_ref, q_ref, nr_ref, *, dtype: str):
    scale = s_ref[0]                                  # (1, 1)
    e = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    qmax = QMAX[dtype]
    y = e / scale
    if dtype == "int8":
        y = jnp.round(y)
    q = jnp.clip(y, -qmax, qmax).astype(q_ref.dtype)
    q_ref[...] = q
    nr_ref[...] = e - q.astype(jnp.float32) * scale


def _dequantize_kernel(s_ref, q_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0]


def _specs(K: int, R: int):
    rb = block_rows(R)
    assert R % rb == 0, (R, rb)
    grid = (K, R // rb)
    scale = pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0))
    rows = pl.BlockSpec((1, rb, LANE), lambda i, j: (i, j, 0))
    return grid, scale, rows


def quantize_ef_fwd(x, residual, *, dtype: str = "int8",
                    interpret: bool = True):
    """x, residual: (K, R, LANE) f32 row views (R a multiple of
    ``block_rows(R)``; zero padding is invisible: it adds nothing to the
    amax, quantizes to 0 and leaves a 0 residual).

    Returns ``(q, new_residual, scale)``: the narrow payload (K, R, LANE),
    the f32 residual (K, R, LANE) and the f32 per-row scales (K,)."""
    K, R, _ = x.shape
    x = x.astype(jnp.float32)
    residual = residual.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x + residual), axis=(1, 2))
    scale = jnp.maximum(amax, SCALE_EPS) / QMAX[dtype]
    grid, s_spec, rows = _specs(K, R)
    q, nr = pl.pallas_call(
        functools.partial(_quantize_ef_kernel, dtype=dtype),
        grid=grid,
        in_specs=[s_spec, rows, rows],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct(x.shape, target_dtype(dtype)),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)],
        interpret=interpret,
    )(scale.reshape(K, 1, 1), x, residual)
    return q, nr, scale


def dequantize_fwd(q, scale, *, interpret: bool = True):
    """q: (K, R, LANE) narrow payload, scale: (K,) f32 -> f32 (K, R, LANE)."""
    K, R, _ = q.shape
    grid, s_spec, rows = _specs(K, R)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[s_spec, rows],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
    )(scale.astype(jnp.float32).reshape(K, 1, 1), q)
