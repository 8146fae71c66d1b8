"""Pure-jnp oracles for the fused quantize+error-feedback kernels.

These are the semantics of record: the Pallas kernels must match them
bit-for-bit (same clip/round/cast ops on the same f32 intermediates), and
the transport codecs fall back to them wherever a Pallas call is
undesirable (sharded multi-pod lowering, property tests over many shapes).

The dtype × granularity matrix mirrors ``kernel.QMAX``:

* ``reference_quantize_ef``   — per-tensor-per-worker scales (reduce over
  every non-leading axis), int8 / fp8_e4m3 / fp8_e5m2 targets, fused
  error-feedback residual;
* ``reference_quantize_axis`` — per-slice scales (reduce over ONE axis,
  keepdims), the primitive the quantized KV pool quantizes heads with;
* ``reference_dequantize``    — payload × broadcastable scale -> f32.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.quantize.kernel import QMAX, target_dtype

SCALE_EPS = 1e-12


def _narrow(e, scale, dtype: str):
    """Shared clip(+round for int targets) + cast; fp8 clips BEFORE the
    cast because e4m3fn saturates to NaN, not inf."""
    qmax = QMAX[dtype]
    y = e / scale
    if dtype == "int8":
        y = jnp.round(y)
    return jnp.clip(y, -qmax, qmax).astype(target_dtype(dtype))


def reference_quantize_ef(x, residual=None, dtype: str = "int8"):
    """Per-row symmetric quantization with error feedback.

    ``x``: (K, ...) f32 — one row per worker; scales reduce over every
    non-leading axis (per-tensor-per-worker).  Returns ``(q, new_residual,
    scale)`` with ``scale`` keepdims-shaped ``(K, 1, ..., 1)``.  Scalar
    (0-d) leaves quantize elementwise; 0-size sentinel leaves pass through
    with unit scales.
    """
    e = x.astype(jnp.float32)
    if residual is not None:
        e = e + residual.astype(jnp.float32)
    axes = tuple(range(1, e.ndim))
    if e.size == 0:
        # 0-size sentinel leaf: nothing to scale — unit scales keep the
        # keepdims shape contract and decode back to the same empty leaf
        scale = jnp.ones(e.shape[:1] + (1,) * len(axes), jnp.float32)
        return e.astype(target_dtype(dtype)), e, scale
    amax = jnp.max(jnp.abs(e), axis=axes, keepdims=True) if axes else \
        jnp.abs(e)
    scale = jnp.maximum(amax, SCALE_EPS) / QMAX[dtype]
    q = _narrow(e, scale, dtype)
    new_residual = e - q.astype(jnp.float32) * scale
    return q, new_residual, scale


def reference_quantize_axis(x, axis: int = -1, dtype: str = "fp8_e4m3"):
    """Per-slice symmetric quantization: one amax scale per slice along
    ``axis`` (keepdims).  No error feedback — this is the KV-pool append
    primitive (axis = head dim -> per-token-per-head scales).  Returns
    ``(q, scale)``.
    """
    e = x.astype(jnp.float32)
    if e.size == 0:
        shape = list(e.shape)
        shape[axis] = 1
        return e.astype(target_dtype(dtype)), jnp.ones(shape, jnp.float32)
    amax = jnp.max(jnp.abs(e), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, SCALE_EPS) / QMAX[dtype]
    return _narrow(e, scale, dtype), scale


def reference_dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def fast_dequant_cast(q):
    """Narrow payload -> f32, bitwise-identical to ``astype(float32)``.

    fp8 -> f32 on CPU XLA lowers to per-element software emulation, which
    dominates the dequant-on-load hot path; a 1-byte payload has only 256
    bit patterns, so the convert is a table gather instead.  int8 and
    wider payloads keep the plain cast (already a vectorized convert)."""
    import jax

    if q.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        table = jnp.arange(256, dtype=jnp.uint8).view(q.dtype).astype(
            jnp.float32)
        return table[jax.lax.bitcast_convert_type(q, jnp.uint8)]
    return q.astype(jnp.float32)
