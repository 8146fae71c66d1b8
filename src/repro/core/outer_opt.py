"""DiLoCo outer synchronization: delta averaging + Nesterov outer SGD.

The outer step (paper §2.2):

    Δθ_i   = θ_i^H − θ_t          (per-worker parameter delta)
    Δθ̄     = (1/k) Σ_i Δθ_i       (cross-worker average — THE communication)
    v_{t+1} = μ v_t + Δθ̄
    θ_{t+1} = θ_t + η v_{t+1}      (Nesterov variant applies μ v + Δθ̄ lookahead)

The cross-worker exchange itself lives in ``repro.core.transport``: deltas
are encoded into ``OuterPayload`` objects by a pluggable ``Codec``
(f32 passthrough / bf16 cast / symmetric int8 with per-tensor scales and
error-feedback residuals), shipped over the replicate hop in the wire
dtype, and decoded back to f32 before the averaging below.  This module
keeps the *optimizer* semantics: plain vs drift-aware averaging and the
Nesterov outer update.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DiLoCoConfig
from repro.core.transport import Transport, make_codec, wire_width

# wire width (bytes/element) of each supported delta payload dtype — a
# compat view of the transport table for older byte-accounting calls
DELTA_WIDTH = {d: wire_width(d)
               for d in ("float32", "bfloat16", "int8", "fp8", "fp8_e5m2")}


class OuterState(NamedTuple):
    v: Any          # momentum pytree (same structure as params)
    t: jax.Array    # outer step counter


def init_outer_state(params) -> OuterState:
    return OuterState(
        v=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        t=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Transport construction + compat wrappers
# ---------------------------------------------------------------------------

def make_transport(cfg: DiLoCoConfig, replicate_fn=None) -> Transport:
    """The transport the config describes.  The Pallas quantize kernels are
    used on the single-device simulation path; mesh paths (``replicate_fn``
    set) fall back to the jnp oracle, which XLA partitions like any other
    elementwise code."""
    codec = make_codec(cfg.delta_dtype, use_kernel=replicate_fn is None)
    return Transport(codec, replicate_fn)


def quantize_delta(delta, dtype: str):
    """Compat wrapper: per-tensor symmetric quantization of a (K, ...)
    stacked delta tree via the codec's jnp oracle.  Returns
    (payload_tree, scales_tree)."""
    payload, _ = make_codec(dtype, use_kernel=False).encode(delta)
    return payload.data, payload.scales


def dequantize_delta(payload, scales):
    if scales is None:
        return jax.tree.map(lambda p: p.astype(jnp.float32), payload)
    return jax.tree.map(lambda p, s: p.astype(jnp.float32) * s,
                        payload, scales)


# ---------------------------------------------------------------------------
# Averaging
# ---------------------------------------------------------------------------

def _tree_dot(a, b) -> jax.Array:
    return sum(jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _mask_rows(mask: jax.Array, ref: jax.Array) -> jax.Array:
    """(K,) mask broadcast against a (K, ...) leaf — reshaped rather than
    indexed, so a traced mask keeps one signature for every live set."""
    return mask.reshape((-1,) + (1,) * (ref.ndim - 1))


def fleet_mask(mask: Optional[jax.Array], k: int, fill: bool) -> jax.Array:
    """``mask``, or the (K,) constant ``fill`` when it is None.

    The default must be built inside the traced function: there it is a
    compile-time constant, so XLA folds every ``where`` over it away and a
    division by the masked count becomes the same multiply by ``1/K`` that
    ``jnp.mean`` lowers to — the whole-fleet program is the unmasked one.
    An array made outside the trace arrives as an argument, which XLA
    cannot fold; one closed over by a scan is a loop operand, which XLA
    need not fold."""
    return jnp.full((k,), fill) if mask is None else mask


def zero_rows(mask: jax.Array, tree) -> Any:
    """``tree`` with the (K, ...) rows that ``mask`` selects set to zero.
    An empty leaf (an optimizer's placeholder) is returned as itself:
    ``jnp.where`` would hand back a fresh empty array, and the program
    would drop that leaf's argument and output."""
    return jax.tree.map(
        lambda x: jnp.where(_mask_rows(mask, x), jnp.zeros_like(x), x)
        if x.size else x, tree)


def masked_mean(mask: jax.Array, x: jax.Array) -> jax.Array:
    """Mean over the rows of ``x`` that ``mask`` selects (at least one row
    counted), in the one form that folds to ``jnp.mean(x, axis=0)`` under
    the all-ones constant mask: a ``where`` then a sum, never a dot with
    the mask."""
    n = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return jnp.sum(jnp.where(_mask_rows(mask, x), x, 0.0), axis=0) / n


def _average(delta, cfg: DiLoCoConfig, live: Optional[jax.Array] = None
             ) -> Any:
    """Decoded f32 (K, ...) stacked deltas -> averaged delta pytree.

    ``live`` is the (K,) bool contribution mask of a quorum round:
    masked-out rows are excluded from the mean (and get -inf drift-aware
    logits).  ``live=None`` is the whole fleet (``fleet_mask``).
    """
    k = jax.tree.leaves(delta)[0].shape[0]
    live = fleet_mask(live, k, True)
    mean = jax.tree.map(lambda d: masked_mean(live, d), delta)
    if not cfg.drift_aware:
        return mean

    # drift-aware: weight workers by cosine(Δ_i, Δ̄), τ = 4
    delta = jax.tree.map(
        lambda d: jnp.where(_mask_rows(live, d), d, 0.0), delta)
    mean_norm = jnp.sqrt(_tree_dot(mean, mean)) + 1e-12

    def cos_i(i):
        di = jax.tree.map(lambda d: d[i], delta)
        ni = jnp.sqrt(_tree_dot(di, di)) + 1e-12
        return _tree_dot(di, mean) / (ni * mean_norm)

    cos = jnp.stack([cos_i(i) for i in range(k)])
    w = jax.nn.softmax(jnp.where(live, 4.0 * cos, -jnp.inf))   # (K,)
    return jax.tree.map(
        lambda d: jnp.tensordot(w, d.astype(jnp.float32), axes=(0, 0)), delta)


def exchange_and_average(stacked_delta, cfg: DiLoCoConfig, replicate_fn=None,
                         residual=None, kind: str = "delta",
                         fragment: int = -1, live=None
                         ) -> Tuple[Any, Optional[Any]]:
    """Full outer-sync data path: encode -> ship -> decode -> average.

    ``residual`` is the per-worker error-feedback carry for lossy codecs
    (None disables error feedback); returns (averaged delta, new residual).
    ``live`` is the optional (K,) quorum contribution mask — see
    ``_average``.
    """
    transport = make_transport(cfg, replicate_fn)
    full, new_residual = transport.exchange(stacked_delta, residual,
                                            kind=kind, fragment=fragment)
    return _average(full, cfg, live=live), new_residual


def average_deltas(stacked_delta, cfg: DiLoCoConfig,
                   replicate_fn=None) -> Any:
    """(K, ...) stacked per-worker deltas -> averaged delta pytree.

    ``replicate_fn(tree)`` reshards the stacked payload to replicated — on a
    pod mesh this is where the inter-pod all-gather happens (in the payload
    dtype).  On a single device it is the identity.
    """
    avg, _ = exchange_and_average(stacked_delta, cfg, replicate_fn)
    return avg


# ---------------------------------------------------------------------------
# Outer update
# ---------------------------------------------------------------------------

def outer_update(global_params, avg_delta, state: OuterState,
                 cfg: DiLoCoConfig) -> Tuple[Any, OuterState]:
    """Nesterov-momentum SGD on the averaged delta (treated as the descent
    direction, i.e. pseudo-gradient = −Δθ̄)."""
    mu, eta = cfg.outer_momentum, cfg.outer_lr

    def upd(p, v, d):
        d = d.astype(jnp.float32)
        v_new = mu * v + d
        step_dir = d + mu * v_new if cfg.nesterov else v_new
        return (p.astype(jnp.float32) + eta * step_dir).astype(p.dtype), v_new

    out = jax.tree.map(upd, global_params, state.v, avg_delta)
    new_params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, OuterState(new_v, state.t + 1)
