"""Muon — momentum + Newton-Schulz orthogonalization (nanochat's default
inner optimizer for weight matrices; the paper keeps it inside DiLoCo).

Newton-Schulz takes one of two paths, chosen per call by
``select_ns_impl`` from what the call can observe (backend, dtype, shape):

* ``pallas`` — ``kernels/newton_schulz``: one Pallas kernel per matrix on
  a TPU, the Gram matrix and the polynomial kept in VMEM, so only the
  iterate touches HBM.  It takes float32 stacks whose last two dims are
  multiples of 128 and whose Gram side fits the kernel's VMEM budget.
* ``xla`` — everything else (every CPU call among them): three batched
  matmuls per iteration, fifteen per step at five iterations.  The Gram
  matrix is taken on the small side of the last two dims: X·Xᵀ for a wide
  (m <= n) matrix, Xᵀ·X for a tall one, each written as a contraction so
  no transpose of X is ever materialised.  The iterations are unrolled,
  so the iterate is no loop carry.  Stacked layer parameters (L, m, n)
  broadcast over the leading dims.

Both round as a float32 matmul at JAX's default precision does on a TPU:
bfloat16 operands, float32 accumulation, a float32 iterate.

Each trace of ``muon``'s update rewrites ``NS_TRACE``: the path every
leaf took and the share of Newton-Schulz FLOPs on the kernel.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Union

import jax
import jax.numpy as jnp

from repro.kernels.newton_schulz import newton_schulz_pallas
from repro.kernels.newton_schulz import plan as ns_plan
from repro.kernels.newton_schulz.kernel import COEFFS, ns_flops
from repro.optim.base import Optimizer

# The last traced update's Newton-Schulz paths: {"paths": {leaf: impl},
# "kernel_flop_share": kernel FLOPs over all Newton-Schulz FLOPs}.
NS_TRACE: Dict[str, object] = {"paths": {}, "kernel_flop_share": 0.0}


def _backend() -> str:
    return jax.default_backend()


def select_ns_impl(*, backend: str, dtype, shape) -> str:
    """The Newton-Schulz path for one call: 'pallas' on a TPU for a float32
    stack of matrices the kernel can plan (sides multiples of 128, Gram
    side within its VMEM budget), 'xla' otherwise."""
    if (backend == "tpu" and jnp.dtype(dtype) == jnp.float32
            and len(shape) >= 2 and math.prod(shape) > 0
            and ns_plan(*shape[-2:]) is not None):
        return "pallas"
    return "xla"


def _mm(x: jax.Array, y: jax.Array, cx: int, cy: int) -> jax.Array:
    """x·y over the last two dims, contracting x's dim ``cx`` with y's dim
    ``cy`` (each -2 or -1); the leading dims are batch dims."""
    batch = tuple(range(x.ndim - 2))
    return jax.lax.dot_general(
        x, y, (((x.ndim + cx,), (y.ndim + cy,)), (batch, batch)),
        preferred_element_type=jnp.float32)


def _newton_schulz_xla(G: jax.Array, steps: int, eps: float) -> jax.Array:
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


def newton_schulz(G: jax.Array, steps: int = 5, eps: float = 1e-7) -> jax.Array:
    """Approximate orthogonalization of the last two dims (quintic NS).

    Wide (m <= n): A = X·Xᵀ, X <- aX + B·X.  Tall: A = Xᵀ·X, X <- aX + X·B,
    the transpose of the wide iteration on Xᵀ (B is symmetric)."""
    if select_ns_impl(backend=_backend(), dtype=G.dtype,
                      shape=G.shape) == "pallas":
        return newton_schulz_pallas(G, steps, eps)
    return _newton_schulz_xla(G, steps, eps)


def muon(lr: Union[float, Callable] = 0.02, momentum: float = 0.95,
         ns_steps: int = 5, nesterov: bool = True) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        return {"mu": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        lr_t = lr_fn(step)
        paths, flops = {}, {"pallas": 0, "xla": 0}

        def upd(path, g, mu):
            if g.ndim < 2:   # sentinel / scalar leaf routed here by mistake
                return jnp.zeros_like(g, jnp.float32), mu
            g = g.astype(jnp.float32)
            mu = momentum * mu + g
            eff = g + momentum * mu if nesterov else mu
            impl = select_ns_impl(backend=_backend(), dtype=eff.dtype,
                                  shape=eff.shape)
            paths[jax.tree_util.keystr(path)] = impl
            flops[impl] += ns_flops(eff.shape, ns_steps)
            with jax.named_scope("newton_schulz"):
                o = newton_schulz(eff, ns_steps)
            # scale: matrices update at spectral-norm-equalized magnitude
            m, n = o.shape[-2], o.shape[-1]
            scale = jnp.sqrt(jnp.maximum(1.0, m / n))
            return -lr_t * scale * o, mu

        out = jax.tree_util.tree_map_with_path(upd, grads, state["mu"])
        total = flops["pallas"] + flops["xla"]
        NS_TRACE.update(paths=paths, kernel_flop_share=(
            flops["pallas"] / total if total else 0.0))
        updates = jax.tree.map(lambda o: o[0], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
        return updates, {"mu": mu}

    return Optimizer(init, update)
