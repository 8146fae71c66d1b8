"""Streaming DiLoCo (Douillard et al., arXiv:2501.18512 — the paper's
reference [4]): instead of synchronizing ALL parameters every H steps,
partition them into F fragments and synchronize one fragment every H/F
steps, staggered.

Each fragment still syncs every H steps (same per-parameter staleness as
vanilla DiLoCo), but the instantaneous inter-pod bandwidth demand drops F×
and the exchange can overlap inner compute — the "distributed free lunch".

Fragmenting follows the layer stack: stacked ``layers/*`` leaves are sliced
into F contiguous layer ranges; non-stacked leaves (embeddings, final norm)
join fragment 0 / F-1 (embedding with the first fragment, head with the
last, mirroring the reference's schedule).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core.diloco import DiLoCoState, DiLoCoTrainer
from repro.core import outer_opt


def _is_stacked(path) -> bool:
    return any(str(getattr(p, "key", "")) == "layers" for p in path)


def fragment_masks(params, num_fragments: int) -> List[Any]:
    """Boolean mask pytrees, one per fragment; stacked layer leaves are
    split along their leading (layer) dim, the rest assigned to the first
    (embeddings) / last (output head) fragment."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    masks = []
    for f in range(num_fragments):
        leaves = []
        for path, leaf in flat:
            keys = [str(getattr(p, "key", "")) for p in path]
            if _is_stacked(path):
                L = leaf.shape[0]
                lo = f * L // num_fragments
                hi = (f + 1) * L // num_fragments
                m = jnp.zeros((L,) + (1,) * (leaf.ndim - 1), bool)
                m = m.at[lo:hi].set(True)
                leaves.append(jnp.broadcast_to(m, leaf.shape))
            else:
                owner = (num_fragments - 1 if any(
                    k in ("final_norm", "unembed") for k in keys) else 0)
                leaves.append(jnp.broadcast_to(jnp.asarray(f == owner),
                                               leaf.shape))
        masks.append(jax.tree_util.tree_unflatten(treedef, leaves))
    return masks


def fragment_fraction(params, mask) -> float:
    tot = sum(x.size for x in jax.tree.leaves(params))
    sel = sum(int(m.sum()) for m in jax.tree.leaves(mask))
    return sel / max(tot, 1)


@dataclasses.dataclass(frozen=True)
class StreamingDiLoCoTrainer(DiLoCoTrainer):
    """DiLoCoTrainer whose outer step touches ONE fragment.

    ``outer_step_fragment(state, frag)`` averages only that fragment's
    deltas, applies the outer Nesterov update to it, and re-broadcasts just
    that slice — the rest of the worker params keep diverging until their
    fragment's slot comes up.
    """
    num_fragments: int = 4

    def fragment_schedule(self) -> int:
        """Steps between fragment syncs (every fragment syncs each H)."""
        return max(self.cfg.h_inner_steps // self.num_fragments, 1)

    def outer_step_fragment_ef(self, state: DiLoCoState, mask, residual=None,
                               contrib=None, adopt=None, reset=None):
        """One fragment's outer sync through the codec transport.  The
        error-feedback residual is masked on the way in and merged on the
        way out, so each element's carry only ever reflects its own
        fragment's quantization error.  Returns (state, new residual).

        The (K,) fleet masks mean what they mean in
        ``DiLoCoTrainer.outer_step_ef``: ``contrib`` rows enter the
        fragment's masked average, ``adopt`` rows take the synced fragment
        slots, ``reset`` rows (rejoiners) take the FULL new global — every
        fragment, regardless of the round's fragment mask — with zeroed
        inner-opt/EF state, and dead rows pass through frozen.  None is
        the whole fleet, folded away at compile time."""
        with jax.named_scope("outer_step"):
            k = self.cfg.num_workers
            rows = outer_opt._mask_rows
            contrib = outer_opt.fleet_mask(contrib, k, True)
            adopt = outer_opt.fleet_mask(adopt, k, True)
            reset = outer_opt.fleet_mask(reset, k, False)
            delta = jax.tree.map(
                lambda w, g, m: (w.astype(jnp.float32)
                                 - g.astype(jnp.float32)[None]) * m[None],
                state.worker_params, state.global_params, mask)
            res_in = residual if residual is None else jax.tree.map(
                lambda r, m: r * m[None], residual, mask)
            avg, new_res = outer_opt.exchange_and_average(
                delta, self.cfg, self.replicate_fn, residual=res_in,
                kind="fragment", live=contrib)
            new_global, new_outer = outer_opt.outer_update(
                state.global_params, avg, state.outer, self.cfg)
            # merge: fragment slots take the synced value, others keep global
            new_global = jax.tree.map(
                lambda ng, g, m: jnp.where(m, ng, g),
                new_global, state.global_params, mask)
            # adopters: fragment slots reset to the synced value, others
            # diverge on; rejoiners take every slot
            new_wp = jax.tree.map(
                lambda w, ng, m: jnp.where(
                    jnp.logical_or(
                        jnp.logical_and(rows(adopt, w), m[None]),
                        rows(reset, w)),
                    ng[None].astype(w.dtype), w),
                state.worker_params, new_global, mask)
            if residual is not None:
                new_res = outer_opt.zero_rows(reset, jax.tree.map(
                    lambda nr, r, m: jnp.where(
                        jnp.logical_and(rows(contrib, r), m[None]), nr, r),
                    new_res, residual, mask))
            return state._replace(
                global_params=new_global, worker_params=new_wp,
                inner_opt=outer_opt.zero_rows(reset, state.inner_opt),
                outer=new_outer), new_res

    def outer_step_fragment(self, state: DiLoCoState, mask) -> DiLoCoState:
        return self.outer_step_fragment_ef(state, mask)[0]

    def bytes_per_fragment_sync(self, params, mask) -> int:
        from repro.core.transport import wire_width
        return int(sum(int(m.sum()) for m in jax.tree.leaves(mask))
                   * wire_width(self.cfg.delta_dtype))


def run_streaming_diloco(trainer: StreamingDiLoCoTrainer, state, data_fn,
                         num_steps: int, record_every: int = 1
                         ) -> Tuple[Any, Dict]:
    """Inner steps with a staggered fragment-sync schedule: fragment
    (t / (H/F)) mod F syncs every H/F steps.  Thin wrapper over the
    unified ``DistTrainer`` runtime."""
    from repro.core.dist_trainer import DistTrainer
    from repro.core.sync import StreamingSync
    dt = DistTrainer(trainer.loss_fn, trainer.opt_cfg, trainer.cfg,
                     StreamingSync(num_fragments=trainer.num_fragments),
                     trainer.replicate_fn)
    return dt.run(state, data_fn, num_steps, record_every=record_every)
