"""Jitted wrapper around the Newton-Schulz kernel: any stack of matrices
in, the kernel's (N, m, n) view inside.

``interpret`` defaults to *backend-selected* via ``repro.kernels.common``:
interpreted on a CPU backend (Mosaic cannot compile there), compiled
everywhere else.  ``plan`` says whether a shape runs here at all; callers
choose the path with it (``repro.optim.muon.select_ns_impl``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.newton_schulz.kernel import newton_schulz_fwd, plan

__all__ = ["newton_schulz_pallas", "plan"]


@functools.partial(jax.jit, static_argnames=("steps", "eps", "interpret"))
def _newton_schulz(G, *, steps, eps, interpret):
    m, n = G.shape[-2:]
    p = plan(m, n)
    if p is None:
        raise ValueError(f"no Newton-Schulz kernel plan for ({m}, {n})")

    # A vmapped call (the inner chunk's worker dim) folds into the stack:
    # the same kernel over more matrices, never a batched kernel body.
    @jax.custom_batching.custom_vmap
    def run(x):
        return newton_schulz_fwd(x.reshape(-1, m, n), steps=steps, eps=eps,
                                 plan=p, interpret=interpret).reshape(x.shape)

    @run.def_vmap
    def _(axis_size, in_batched, x):
        return run(x), in_batched[0]

    return run(G.astype(jnp.float32))


def newton_schulz_pallas(G, steps: int = 5, eps: float = 1e-7, *,
                         interpret: Optional[bool] = None):
    """Orthogonalize the last two dims of G (..., m, n), both multiples of
    128, with ``plan(m, n)`` not None.  Float32 out; same rounding as
    ``ref.reference_newton_schulz``."""
    return _newton_schulz(G, steps=steps, eps=eps,
                          interpret=resolve_interpret(interpret))
