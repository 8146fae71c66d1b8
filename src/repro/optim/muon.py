"""Muon — momentum + Newton-Schulz orthogonalization (nanochat's default
inner optimizer for weight matrices; the paper keeps it inside DiLoCo).

Newton-Schulz is five batched matmuls per step — MXU-native on TPU, no custom
kernel needed.  Stacked layer parameters (L, m, n) are handled by broadcasting
the matmuls over the leading dim.
"""
from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp

from repro.optim.base import Optimizer

_NS_COEFFS = (3.4445, -4.7750, 2.0315)


def newton_schulz(G: jax.Array, steps: int = 5, eps: float = 1e-7) -> jax.Array:
    """Approximate orthogonalization of the last two dims (quintic NS)."""
    a, b, c = _NS_COEFFS
    X = G.astype(jnp.float32)
    transposed = X.shape[-2] > X.shape[-1]
    if transposed:
        X = jnp.swapaxes(X, -1, -2)
    norm = jnp.sqrt(jnp.sum(jnp.square(X), axis=(-2, -1), keepdims=True))
    X = X / (norm + eps)

    def body(X, _):
        A = X @ jnp.swapaxes(X, -1, -2)
        B = b * A + c * (A @ A)
        return a * X + B @ X, None

    X, _ = jax.lax.scan(body, X, None, length=steps)
    if transposed:
        X = jnp.swapaxes(X, -1, -2)
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
