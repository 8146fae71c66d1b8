"""Jitted wrapper for the fused AdamW kernel: arbitrary leaf shapes in,
flattened LANE-padded (1, M) kernel views inside.

``interpret`` defaults to *backend-selected* via
``repro.kernels.common``: interpreted on a CPU backend (Mosaic cannot
compile there), compiled everywhere else.

Zero padding is invisible to the update: padded lanes carry g=m=v=p=0, so
m'=v'=0 and u = -lr*(0/(0+eps) + 0) = 0, and they are sliced away anyway.
0-sized sentinel leaves (the partitioned optimizer masks leaves it does
not own to ``(0,)``) short-circuit to the oracle — a Pallas grid cannot
be empty.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import (default_interpret, pallas_mode,
                                  resolve_interpret)
from repro.kernels.fused_adamw.kernel import LANE, fused_adamw_fwd
from repro.kernels.fused_adamw.ref import reference_fused_adamw

__all__ = ["fused_adamw_update", "default_interpret", "pallas_mode"]


def _flatten_pad(x, dtype=None) -> jax.Array:
    flat = x.reshape(1, -1)
    if dtype is not None:
        flat = flat.astype(dtype)
    pad = (-flat.shape[1]) % LANE
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "interpret"))
def _fused_update(p, g, m, v, lr, bc1, bc2, *, b1, b2, eps, wd, interpret):
    if p.size == 0:
        return reference_fused_adamw(p, g, m, v, lr, bc1, bc2,
                                     b1=b1, b2=b2, eps=eps, wd=wd)
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(bc1, jnp.float32),
                      jnp.asarray(bc2, jnp.float32)]).reshape(1, 3)
    u, nm, nv = fused_adamw_fwd(
        _flatten_pad(p), _flatten_pad(g), _flatten_pad(m, jnp.float32),
        _flatten_pad(v, jnp.float32), scal,
        b1=b1, b2=b2, eps=eps, wd=wd, interpret=interpret)
    n = p.size
    unflat = lambda x: x[0, :n].reshape(p.shape)
    return unflat(u), unflat(nm), unflat(nv)


def fused_adamw_update(p, g, m, v, lr, bc1, bc2, *, b1: float, b2: float,
                       eps: float, wd: float,
                       interpret: Optional[bool] = None):
    """One fused AdamW step on a single leaf of any shape/float dtype.

    ``lr``/``bc1``/``bc2`` are (possibly traced) f32 scalars — the
    schedule value and bias corrections ``1 - b**t``.  Returns
    ``(update, new_m, new_v)`` shaped like the jnp oracle
    (``ref.reference_fused_adamw``): same ops in the same order as the
    unfused ``repro.optim.adamw`` math, agreeing to within ~1-2 ulp of
    FMA-contraction noise (see ``ref.py``).
    """
    interpret = resolve_interpret(interpret)
    return _fused_update(p, g, m, v, lr, bc1, bc2, b1=b1, b2=b2, eps=eps,
                         wd=wd, interpret=interpret)
