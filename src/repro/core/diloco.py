"""DiLoCo trainer — the paper's core contribution as a composable JAX module.

The trainer wraps ANY loss function (the full nanochat-style pipeline, or one
of the ten assigned architectures) exactly like the paper wraps nanochat's
training loop:

    each worker:  H inner steps (AdamW+Muon)   — no cross-worker traffic
    every H:      average parameter deltas, outer Nesterov SGD, re-broadcast

Workers are encoded as a leading ``K`` dimension on params / optimizer state,
and the inner step is ``jax.vmap`` of the single-worker step: K workers
vmapped on one device, a bit-faithful simulation of the algorithm.  Sharding
the K dim over a device axis, one worker per chip, is ROADMAP R1; no launcher
builds that today.

The DDP baseline (``repro.core.ddp``) is the same inner step with K=1 and the
global batch, synchronizing every step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DiLoCoConfig, OptimizerConfig
from repro.core import outer_opt
from repro.core.outer_opt import OuterState
from repro.optim import apply_updates, nanochat_optimizer
from repro.optim.base import Optimizer


class DiLoCoState(NamedTuple):
    global_params: Any        # θ_t — the synchronized snapshot
    outer: OuterState
    worker_params: Any        # (K, ...) per-worker divergent copies
    inner_opt: Any            # (K, ...) per-worker inner optimizer state
    inner_step: jax.Array     # total inner steps taken (scalar int32)


def _broadcast(tree, k: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (k,) + x.shape), tree)


@dataclasses.dataclass(frozen=True)
class DiLoCoTrainer:
    """loss_fn(params, batch) -> (loss, metrics-dict)."""
    loss_fn: Callable
    opt_cfg: OptimizerConfig
    cfg: DiLoCoConfig
    replicate_fn: Optional[Callable] = None   # mesh: reshard stacked->replicated

    # -- construction -------------------------------------------------------
    def init(self, params) -> DiLoCoState:
        k = self.cfg.num_workers
        inner = self._inner_opt()
        worker_params = _broadcast(params, k)
        inner_state = jax.vmap(inner.init)(worker_params)
        return DiLoCoState(
            global_params=params,
            outer=outer_opt.init_outer_state(params),
            worker_params=worker_params,
            inner_opt=inner_state,
            inner_step=jnp.zeros((), jnp.int32))

    def _inner_opt(self) -> Optimizer:
        return nanochat_optimizer(self.opt_cfg)

    # -- inner step ----------------------------------------------------------
    def _one_worker_step(self, params, opt_state, batch, step):
        # ``model`` sits inside the differentiated function, so autodiff
        # names the backward ``transpose(jvp(model))``
        (loss, metrics), grads = jax.value_and_grad(
            jax.named_scope("model")(self.loss_fn), has_aux=True)(
                params, batch)
        with jax.named_scope("inner_opt"):
            updates, opt_state = self._inner_opt().update(
                grads, opt_state, params, step)
            return apply_updates(params, updates), opt_state, loss, metrics

    def inner_step(self, state: DiLoCoState, batches) -> Tuple[DiLoCoState, jax.Array, Dict]:
        """batches: pytree with leading (K, ...) — one shard per worker."""
        new_wp, new_opt, loss, metrics = jax.vmap(
            self._one_worker_step, in_axes=(0, 0, 0, None))(
                state.worker_params, state.inner_opt, batches,
                state.inner_step)
        return (state._replace(worker_params=new_wp, inner_opt=new_opt,
                               inner_step=state.inner_step + 1),
                loss, metrics)

    def inner_chunk(self, state: DiLoCoState, batches, live=None
                    ) -> Tuple[DiLoCoState, jax.Array]:
        """Scan-fused run of T inner steps — the device-speed hot path.

        ``batches`` carry a leading (T, K, ...) time dim; the scan compiles
        ONE program for the whole chunk, so the T per-step dispatches (and
        their host round-trips) collapse into a single device call.
        Returns ``(state, losses)`` with ``losses`` the (T, K) per-worker
        per-step losses — fetched once per chunk by the caller, never per
        step.  The losses leave the program RAW, exactly like the
        per-step jit's loss output: reducing them on device here would
        let XLA fuse (and reassociate) the loss reduction differently
        than the per-step program does, breaking recorded-loss
        bit-exactness; the worker mean is instead taken on the host in a
        fixed order (``dist_trainer._host_mean``) in both loops.

        ``live`` is an optional (K,) bool liveness mask: dead rows' params
        and optimizer state pass through frozen (a traced argument, so a
        changing live set never retraces); their losses are still in the
        (T, K) output and the trainer leaves them out of the recorded
        mean.  ``None`` is the whole fleet, a constant built inside the
        scanned body so that XLA folds the merge away (a mask closed over
        by the scan would be a loop operand it need not fold).

        The scan carry holds ONLY what the inner step mutates
        (``worker_params``, ``inner_opt``, ``inner_step``);
        ``global_params`` and the outer-optimizer state are loop-invariant
        closures, so XLA hoists them instead of threading (and on some
        backends copying) them through every iteration.
        """
        rows = outer_opt._mask_rows

        def body(carry, batch):
            wp, opt, istep = carry
            st = state._replace(worker_params=wp, inner_opt=opt,
                                inner_step=istep)
            st, loss, _ = self.inner_step(st, batch)
            m = outer_opt.fleet_mask(live, self.cfg.num_workers, True)
            new_wp, new_opt = jax.tree.map(
                lambda n, o: jnp.where(rows(m, n), n, o),
                (st.worker_params, st.inner_opt), (wp, opt))
            return (new_wp, new_opt, st.inner_step), loss

        carry = (state.worker_params, state.inner_opt, state.inner_step)
        (wp, opt, istep), losses = jax.lax.scan(body, carry, batches)
        return (state._replace(worker_params=wp, inner_opt=opt,
                               inner_step=istep), losses)

    # -- outer step ----------------------------------------------------------
    def init_residual(self, params):
        """Per-worker (K, ...) error-feedback residual for lossy codecs, or
        None when the codec is lossless / error feedback is disabled.  Held
        host-side by the sync runners, NOT in ``DiLoCoState`` — checkpoints
        and the multi-pod abstract state stay unchanged."""
        from repro.core.transport import make_codec
        if not (self.cfg.error_feedback
                and make_codec(self.cfg.delta_dtype).lossy):
            return None
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        return _broadcast(zeros, self.cfg.num_workers)

    def outer_step_ef(self, state: DiLoCoState, residual=None,
                      contrib=None, adopt=None, reset=None):
        """Outer sync through the codec transport with an optional
        error-feedback residual; returns (new state, new residual).

        The (K,) bool fleet masks of a quorum round (traced, so a changing
        live set never retraces):

        * ``contrib`` — rows whose deltas enter the masked average;
        * ``adopt``   — live rows that take the new anchor (keeps their
          inner optimizer state, exactly like a normal sync);
        * ``reset``   — rejoiners: take the new anchor AND restart inner
          optimizer + error-feedback state from zero (AdamW/Muon moments
          init to zeros, so zeroing IS re-initialization);
        * rows in none of the masks (dead workers) pass through frozen.

        Left None they mean the whole fleet (all contribute and adopt,
        none resets): constants built here, inside the trace, which XLA
        folds away (``outer_opt.fleet_mask``)."""
        with jax.named_scope("outer_step"):
            k = self.cfg.num_workers
            rows = outer_opt._mask_rows
            contrib = outer_opt.fleet_mask(contrib, k, True)
            adopt = outer_opt.fleet_mask(adopt, k, True)
            reset = outer_opt.fleet_mask(reset, k, False)
            delta = jax.tree.map(
                lambda w, g: (w.astype(jnp.float32)
                              - g.astype(jnp.float32)[None]),
                state.worker_params, state.global_params)
            avg, new_residual = outer_opt.exchange_and_average(
                delta, self.cfg, self.replicate_fn, residual=residual,
                live=contrib)
            new_global, new_outer = outer_opt.outer_update(
                state.global_params, avg, state.outer, self.cfg)
            # adopters take the synchronized params; inner optimizer state
            # is kept per-worker across syncs (paper §3 — AdamW/Muon state
            # is local)
            take = jnp.logical_or(adopt, reset)
            new_wp = jax.tree.map(
                lambda g, o: jnp.where(rows(take, o), g[None], o),
                new_global, state.worker_params)
            if new_residual is not None:
                # non-contributors never shipped, so their EF carry is
                # unchanged; rejoiners restart with a clean carry
                new_residual = outer_opt.zero_rows(reset, jax.tree.map(
                    lambda n, o: jnp.where(rows(contrib, n), n, o),
                    new_residual, residual))
            return state._replace(
                global_params=new_global, worker_params=new_wp,
                inner_opt=outer_opt.zero_rows(reset, state.inner_opt),
                outer=new_outer), new_residual

    def outer_step(self, state: DiLoCoState) -> DiLoCoState:
        return self.outer_step_ef(state)[0]

    # -- elastic rejoin -------------------------------------------------------
    def adopt_anchor(self, state: DiLoCoState, residual, reset):
        """Rejoin without a round (quorum skipped): ``reset`` rows adopt
        the CURRENT anchor with zeroed inner-opt/EF state; the anchor and
        outer momentum are untouched."""
        rows = outer_opt._mask_rows
        new_wp = jax.tree.map(
            lambda g, o: jnp.where(rows(reset, o), g[None], o),
            state.global_params, state.worker_params)
        if residual is not None:
            residual = outer_opt.zero_rows(reset, residual)
        return state._replace(
            worker_params=new_wp,
            inner_opt=outer_opt.zero_rows(reset, state.inner_opt)), residual

    # -- jitted entry points ---------------------------------------------------
    def jit_steps(self):
        return jax.jit(self.inner_step), jax.jit(self.outer_step)

    # -- communication accounting (paper: "communication reduced ~100x") ------
    def bytes_per_sync(self, params) -> int:
        """Bytes each worker ships per outer sync (payload dtype)."""
        from repro.core.transport import wire_width
        n = sum(x.size for x in jax.tree.leaves(params))
        return n * wire_width(self.cfg.delta_dtype)

    def ddp_bytes_per_step(self, params) -> int:
        """What synchronous DDP would ship per *inner* step (fp32 grads)."""
        return sum(x.size for x in jax.tree.leaves(params)) * 4


# ---------------------------------------------------------------------------
# Training loop — thin wrapper over the unified DistTrainer runtime
# ---------------------------------------------------------------------------

def run_diloco(trainer: DiLoCoTrainer, state: DiLoCoState, data_fn,
               num_steps: int, h_schedule=None,
               record_every: int = 1,
               eval_fn: Optional[Callable] = None,
               eval_every: int = 0) -> Tuple[DiLoCoState, Dict]:
    """data_fn(step) -> per-worker-stacked batch pytree.

    ``h_schedule`` decides when to synchronize (defaults to fixed H from the
    config); supports the adaptive-H controller (paper §5 future work).
    """
    from repro.core.dist_trainer import DistTrainer
    from repro.core.sync import DiLoCoSync
    dt = DistTrainer(trainer.loss_fn, trainer.opt_cfg, trainer.cfg,
                     DiLoCoSync(h_schedule=h_schedule), trainer.replicate_fn)
    return dt.run(state, data_fn, num_steps, record_every=record_every,
                  eval_fn=eval_fn, eval_every=eval_every)
