"""Pure-jnp oracle for the Newton-Schulz kernel, with its rounding points
written out.

A float32 matmul at JAX's default precision on a TPU takes bfloat16
operands and accumulates in float32.  Here each matmul casts its operands
to bfloat16 explicitly, so the oracle rounds where the kernel and the XLA
path on a TPU round, on any backend:

    X0 = G / (||G||_F + eps)
    A  = bf16(X)·bf16(X)ᵀ                     (f32 accumulation)
    B  = b·A + c·bf16(A)·bf16(A)              (b·A on the f32 A)
    X <- a·X + bf16(B)·bf16(X)                (a·X on the f32 X)

Tall matrices (m > n) take the Gram matrix on the small side:
A = Xᵀ·X and X <- a·X + X·B.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.newton_schulz.kernel import COEFFS


def _mm(x, y, cx: int, cy: int):
    batch = tuple(range(x.ndim - 2))
    return jax.lax.dot_general(
        x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
        (((x.ndim + cx,), (y.ndim + cy,)), (batch, batch)),
        preferred_element_type=jnp.float32)


def reference_newton_schulz(G, steps: int = 5, eps: float = 1e-7):
    """Orthogonalize the last two dims of G with bfloat16 matmul operands."""
    a, b, c = COEFFS
    X = G.astype(jnp.float32)
    tall = X.shape[-2] > X.shape[-1]
    norm = jnp.sqrt(jnp.sum(jnp.square(X), axis=(-2, -1), keepdims=True))
    X = X / (norm + eps)
    for _ in range(steps):
        A = _mm(X, X, -2, -2) if tall else _mm(X, X, -1, -1)
        B = b * A + c * _mm(A, A, -1, -2)
        X = a * X + (_mm(X, B, -1, -2) if tall else _mm(B, X, -1, -2))
    return X
