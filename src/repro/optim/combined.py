"""nanochat's optimizer split: **Muon** for transformer weight matrices,
**AdamW** for embeddings / unembedding / norms / biases / SSM scalars /
depthwise conv filters.  The paper keeps exactly this split inside each
DiLoCo worker ("Inner optimizers: AdamW and Muon (default in nanochat)").
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig
from repro.optim.adamw import adamw
from repro.optim.base import Optimizer, clip_by_global_norm
from repro.optim.muon import muon
from repro.optim.schedule import lr_schedule

_ADAM_LEAF_NAMES = {"A_log", "D", "dt_bias", "conv_w", "conv_b", "router",
                    "table", "unembed", "scale", "bias", "norm_scale",
                    "mix_a", "mix_s", "bq", "bk", "bv"}


def partition_label(path, leaf) -> str:
    """'muon' for true weight matrices, 'adamw' for everything else."""
    keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
    if any(k in _ADAM_LEAF_NAMES for k in keys):
        return "adamw"
    if any(k == "embed" for k in keys):
        return "adamw"
    if leaf.ndim < 2:
        return "adamw"
    return "muon"


def _mask(tree, label_fn, want: str):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: label_fn(path, leaf) == want, tree)


_SENTINEL_SHAPE = (0,)


def _masked_tree(tree, mask):
    """Replace masked-out leaves with 0-sized sentinels so per-label optimizer
    state is only allocated for the leaves that label actually owns."""
    return jax.tree.map(
        lambda x, m: x if m else jnp.zeros(_SENTINEL_SHAPE, jnp.float32),
        tree, mask)


def partitioned(opts: dict, label_fn: Callable) -> Optimizer:
    """Route each leaf to the optimizer chosen by ``label_fn(path, leaf)``."""
    labels = sorted(opts)

    def init(params):
        return {lab: opts[lab].init(_masked_tree(params, _mask(params, label_fn, lab)))
                for lab in labels}

    def update(grads, state, params, step):
        total = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)
        new_state = {}
        for lab in labels:
            mask = _mask(grads, label_fn, lab)
            with jax.named_scope(lab):
                upd, new_state[lab] = opts[lab].update(
                    _masked_tree(grads, mask), state[lab],
                    _masked_tree(params, mask), step)
            total = jax.tree.map(
                lambda acc, u, m: acc + u.astype(jnp.float32) if m else acc,
                total, upd, mask)
        return total, new_state

    return Optimizer(init, update)


def nanochat_optimizer(cfg: OptimizerConfig) -> Optimizer:
    muon_lr = lr_schedule(cfg.schedule, cfg.learning_rate, cfg.total_steps,
                          cfg.warmup_steps, cfg.final_lr_frac)
    adam_lr = lr_schedule(cfg.schedule, cfg.adam_lr, cfg.total_steps,
                          cfg.warmup_steps, cfg.final_lr_frac)
    inner = partitioned(
        {"muon": muon(muon_lr, cfg.muon_momentum, cfg.muon_ns_steps),
         "adamw": adamw(adam_lr, cfg.adam_betas, cfg.adam_eps,
                        cfg.weight_decay, fused=cfg.fused_adamw)},
        partition_label)

    if cfg.grad_clip <= 0:
        return inner

    def update(grads, state, params, step):
        with jax.named_scope("clip"):
            grads, _ = clip_by_global_norm(grads, cfg.grad_clip)
        return inner.update(grads, state, params, step)

    return Optimizer(inner.init, update)
