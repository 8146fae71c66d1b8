"""Jitted wrappers for the fused RMSNorm kernel (reshape any leading dims).

``interpret`` defaults to *backend-selected* via ``repro.kernels.common``:
interpreted on a CPU backend, compiled everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.common import resolve_interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd, rmsnorm_residual_fwd


def _row_block(shape) -> int:
    """Largest power-of-two row tile (<= 256) dividing the row count."""
    R = 1
    for s in shape[:-1]:
        R *= s
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if R % cand == 0:
            return cand
    return R


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rmsnorm(x, scale, *, eps, interpret):
    shape = x.shape
    R = 1
    for s in shape[:-1]:
        R *= s
    out = rmsnorm_fwd(x.reshape(R, shape[-1]), scale, eps=eps,
                      br=_row_block(shape), interpret=interpret)
    return out.reshape(shape)


def rmsnorm(x, scale, *, eps: float = 1e-5,
            interpret: Optional[bool] = None):
    interpret = resolve_interpret(interpret)
    return _rmsnorm(x, scale, eps=eps, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rmsnorm_residual(x, residual, scale, *, eps, interpret):
    shape = x.shape
    R = 1
    for s in shape[:-1]:
        R *= s
    o, r = rmsnorm_residual_fwd(x.reshape(R, shape[-1]),
                                residual.reshape(R, shape[-1]), scale,
                                eps=eps, br=_row_block(shape),
                                interpret=interpret)
    return o.reshape(shape), r.reshape(shape)


def rmsnorm_residual(x, residual, scale, *, eps: float = 1e-5,
                     interpret: Optional[bool] = None):
    interpret = resolve_interpret(interpret)
    return _rmsnorm_residual(x, residual, scale, eps=eps,
                             interpret=interpret)
