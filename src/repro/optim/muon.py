"""Muon — momentum + Newton-Schulz orthogonalization (nanochat's default
inner optimizer for weight matrices; the paper keeps it inside DiLoCo).

Newton-Schulz is three batched matmuls per iteration, fifteen per step at
five iterations — MXU-native on TPU, no custom kernel needed.  The Gram
matrix is taken on the small side of the last two dims: X·Xᵀ for a wide
(m <= n) matrix, Xᵀ·X for a tall one, each written as a contraction so no
transpose of X is ever materialised.  The iterations are unrolled, so the
iterate is no loop carry.  Stacked layer parameters (L, m, n) broadcast
over the leading dims.
"""
from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp

from repro.optim.base import Optimizer

_NS_COEFFS = (3.4445, -4.7750, 2.0315)


def _mm(x: jax.Array, y: jax.Array, cx: int, cy: int) -> jax.Array:
    """x·y over the last two dims, contracting x's dim ``cx`` with y's dim
    ``cy`` (each -2 or -1); the leading dims are batch dims."""
    batch = tuple(range(x.ndim - 2))
    return jax.lax.dot_general(
        x, y, (((x.ndim + cx,), (y.ndim + cy,)), (batch, batch)),
        preferred_element_type=jnp.float32)


def newton_schulz(G: jax.Array, steps: int = 5, eps: float = 1e-7) -> jax.Array:
    """Approximate orthogonalization of the last two dims (quintic NS).

    Wide (m <= n): A = X·Xᵀ, X <- aX + B·X.  Tall: A = Xᵀ·X, X <- aX + X·B,
    the transpose of the wide iteration on Xᵀ (B is symmetric)."""
    a, b, c = _NS_COEFFS
    X = G.astype(jnp.float32)
    tall = X.shape[-2] > X.shape[-1]
    norm = jnp.sqrt(jnp.sum(jnp.square(X), axis=(-2, -1), keepdims=True))
    X = X / (norm + eps)
    for _ in range(steps):
        A = _mm(X, X, -2, -2) if tall else _mm(X, X, -1, -1)
        B = b * A + c * _mm(A, A, -1, -2)
        X = a * X + (_mm(X, B, -1, -2) if tall else _mm(B, X, -1, -2))
    return X


def muon(lr: Union[float, Callable] = 0.02, momentum: float = 0.95,
         ns_steps: int = 5, nesterov: bool = True) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        return {"mu": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        lr_t = lr_fn(step)

        def upd(g, mu):
            if g.ndim < 2:   # sentinel / scalar leaf routed here by mistake
                return jnp.zeros_like(g, jnp.float32), mu
            g = g.astype(jnp.float32)
            mu = momentum * mu + g
            eff = g + momentum * mu if nesterov else mu
            with jax.named_scope("newton_schulz"):
                o = newton_schulz(eff, ns_steps)
            # scale: matrices update at spectral-norm-equalized magnitude
            m, n = o.shape[-2], o.shape[-1]
            scale = jnp.sqrt(jnp.maximum(1.0, m / n))
            return -lr_t * scale * o, mu

        out = jax.tree.map(upd, grads, state["mu"])
        updates = jax.tree.map(lambda o: o[0], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
        return updates, {"mu": mu}

    return Optimizer(init, update)
