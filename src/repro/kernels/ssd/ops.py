"""Jitted wrapper for the SSD Pallas kernel (pads S to a chunk multiple).

``interpret`` defaults to *backend-selected* via ``repro.kernels.common``:
interpreted on a CPU backend, compiled everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.ssd.kernel import ssd_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd(x, dt, A, Bm, Cm, D, *, chunk, interpret):
    """Pads to a chunk multiple with dt=0 (decay 1, zero input — a no-op for
    the recurrence), runs the kernel, strips padding."""
    S = x.shape[1]
    Q = min(chunk, S) if S % chunk else chunk
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, h = ssd_fwd(x, dt, A, Bm, Cm, D, chunk=Q, interpret=interpret)
    return y[:, :S], h


def ssd(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
        interpret: Optional[bool] = None):
    interpret = resolve_interpret(interpret)
    return _ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=interpret)
