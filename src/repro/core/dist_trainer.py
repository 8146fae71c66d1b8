"""The unified distributed-training loop.

One loop runs every configuration the paper compares (and the ones it
proposes as future work): the strategy object owns *when and what* to
synchronize, the loop owns everything else — vmapped inner steps, loss
recording, eval hooks, history.  ``run_ddp`` / ``run_diloco`` /
``run_streaming_diloco`` remain as thin wrappers over this loop.

    trainer = DistTrainer(model.loss, opt_cfg, dcfg, DiLoCoSync())
    state = trainer.init(params)
    state, hist = trainer.run(state, data_fn, num_steps)

History keys: ``step`` / ``loss`` (every ``record_every``), ``sync_steps``
(full outer exchanges), ``frag_syncs`` (``(step, fragment)`` pairs),
``evals`` (``(step, eval_fn(global_params))`` pairs), ``step_seconds``
(median measured seconds per inner step — robust to jit-compile spikes;
feeds the comm simulator's calibration).

The hot path (chunked execution)
--------------------------------
DiLoCo's premise is that the H local steps dominate wall-clock while sync
is rare — so the device must never wait on Python between syncs.  The
default ``chunked=True`` loop makes that true:

* **chunk = steps to the next sync event.**  Each ``SyncRunner`` exposes
  ``next_event(step)`` — the next step whose ``after_step`` touches device
  state (an outer sync, a delayed apply, a straggler snapshot).  The loop
  ``lax.scan``s the inner step from the current step to exactly that
  boundary (further split by ``eval_every`` and ``num_steps``), so one
  device dispatch replaces ~H per-step dispatches.  For DiLoCo the chunk
  boundaries ARE the H boundaries; for streaming/pipelined schedules the
  fragment events fire at the same steps they would per-step.  Runners on
  per-worker event clocks (async gossip: worker i syncs every ``H + j_i``
  steps) report the MIN over workers' next boundaries, so a chunk ends
  whenever ANY worker is due — the contract is per-runner, not per-fleet.
* **one fetch per chunk.**  Per-step per-worker losses come back as one
  (T, K) device array fetched with a single ``device_get``; ``after_step``
  is then replayed per step on the host with fixed-order means of those
  rows (between events it is pure bookkeeping by contract, see
  ``SyncRunner``), so histories —
  ``step``/``loss``/``sync_steps``/``frag_syncs``/``evals``, plus any
  runner-defined keys such as gossip's ``gossip_syncs`` (lists are
  created on demand) — are bit-identical to the per-step loop's.
* **buffer donation.**  The chunk jit donates the state (params, momenta,
  and optimizer moments update in place on accelerators), as do the
  runners' outer-step jits.  ``run`` defensively copies the caller's
  state once at entry so the passed-in state object survives the run.
* **async prefetch.**  ``prefetch=N`` sources batches from a background
  ``repro.data.pipeline.Prefetcher`` that assembles batches up to N steps
  ahead (one stacked ``device_put`` per chunk at take time), overlapping
  host data work with device compute.  At every chunk boundary the loop
  additionally ``prime``s the next chunk, so its host stack +
  ``device_put`` overlap the outer-sync jit dispatched at the boundary
  instead of serializing behind it (``take`` falls back losslessly if a
  runner shifts the predicted bounds).
* ``step_seconds`` is each chunk's wall-clock divided by its length
  (median over chunks), preserving the comm-simulator calibration
  contract.

``chunked=False`` keeps the original per-step loop — the reference the
bit-exactness tests (and ``benchmarks/train_bench.py``) compare against.

Fault tolerance
---------------
One program per strategy serves fault-free and faulty runs alike.  The
chunk and the outer steps take the fleet's (K,) masks (live, contributing,
adopting, rejoining workers) as optional arguments; left out, they default
to the whole fleet as constants built inside the trace, which XLA folds
away, so a run without a fault schedule compiles the unmasked programs.
Under a fault schedule the loop passes the chunk a live mask only while a
worker is down, and a runner bound to the ``FleetTracker`` passes each
round's masks to its outer step.

Profiling
---------
The chunked loop and the programs it runs name their parts for JAX's own
profiler.  Wrap a run in ``jax.profiler.trace(log_dir)`` and open the
``.xplane.pb`` it writes in TensorBoard's profile plugin, or its trace in
Perfetto; the host spans and the device ops share the profiler's clock.

Host spans (``jax.profiler.TraceAnnotation``), one set per chunk, inside a
``jax.profiler.StepTraceAnnotation("train", step_num=<chunk index>)``:

* ``trainer.data``       — ``data_fn`` + ``stack_batches``, or the
  prefetcher's ``take`` (and its ``prime`` of the next chunk);
* ``trainer.chunk``      — the ``inner_chunk`` dispatch, with args
  ``first_step`` and ``steps``;
* ``trainer.fetch``      — the one loss fetch, which waits on the device;
* ``trainer.sync``       — the ``after_step`` replay, which dispatches any
  outer step;
* ``trainer.checkpoint`` and ``trainer.eval`` where those run.

Device scopes (``jax.named_scope``), in the ops' ``op_name`` metadata:

* ``model`` — the loss that ``value_and_grad`` differentiates, with
  ``attention``, ``mlp`` (``models.transformer._block_fwd``) and
  ``lm_head`` (the chunked cross-entropy) inside it.  Autodiff names the
  backward ``transpose(jvp(model))``; the layer scan's remat recompute
  runs inside that backward;
* ``inner_opt`` — the optimizer update and its application, with
  ``clip``, ``muon`` (``newton_schulz`` inside it) and ``adamw`` inside;
* ``outer_step`` — the DiLoCo and Streaming DiLoCo outer-step programs,
  ``outer_step_ef`` and ``outer_step_fragment_ef``.

With the profiler off a span is one inactive ``TraceMe`` and a scope is
compile-time metadata only.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.base import DiLoCoConfig, OptimizerConfig
from repro.core.diloco import DiLoCoState
from repro.core.faults import FaultSchedule, FleetTracker, SimulatedCrash
from repro.core.streaming import StreamingDiLoCoTrainer
from repro.core.sync import SyncStrategy

# the loop's single deliberate device->host read per chunk — module-level so
# the one-fetch guard test can count calls
_fetch = jax.device_get

# a host span of the chunked loop (module docstring, "Profiling")
_span = jax.profiler.TraceAnnotation

# CPU backends ignore donation for some buffers; the advisory warning would
# fire once per compiled chunk length.  Applied via catch_warnings inside
# run() only — a library import must not rewrite global warning filters.
_DONATION_WARNING = "Some donated buffers were not usable"


def _host_mean(row: np.ndarray, live=None) -> float:
    """Worker-mean of a fetched (K,) loss row, in a FIXED summation order.

    Both loops record means of the RAW per-worker losses their jits
    output; reducing on device would let XLA pick a different reduce
    association per program (eager op vs scan body — a 1-ulp wobble that
    breaks chunked-vs-per-step bit-exactness and, through ``AdaptiveH``'s
    loss window, could even flip a sync decision).  Host IEEE f32 adds in
    index order are deterministic everywhere.  ``live`` leaves the dead
    workers' entries out: their frozen params' losses are not part of the
    fleet's trajectory.
    """
    idx = [w for w in range(len(row)) if live is None or live[w]]
    if not idx:
        return float("nan")
    acc = row[idx[0]]
    for w in idx[1:]:
        acc = acc + row[w]
    return float(acc / row.dtype.type(len(idx)))


def _history_from_json(v):
    """JSON round-trips tuples as lists; restore the tuples history
    consumers (and the resume bit-exactness tests) expect."""
    if isinstance(v, list):
        return tuple(_history_from_json(x) for x in v)
    return v


@dataclasses.dataclass(frozen=True)
class DistTrainer:
    """loss_fn(params, batch) -> (loss, metrics-dict); batches carry a
    leading (K, ...) worker dim (K=1 for DDP with the global batch)."""
    loss_fn: Callable
    opt_cfg: OptimizerConfig
    cfg: DiLoCoConfig
    strategy: SyncStrategy
    replicate_fn: Optional[Callable] = None

    # The compute engine: StreamingDiLoCoTrainer is the most general
    # DiLoCoTrainer (inner step + full outer step + fragment outer step);
    # strategies pick which pieces they drive.
    def engine(self) -> StreamingDiLoCoTrainer:
        return StreamingDiLoCoTrainer(
            self.loss_fn, self.opt_cfg, self.cfg, self.replicate_fn,
            num_fragments=getattr(self.strategy, "num_fragments", 4))

    def init(self, params) -> DiLoCoState:
        return self.engine().init(params)

    def run(self, state: DiLoCoState, data_fn, num_steps: int,
            record_every: int = 1, eval_fn: Optional[Callable] = None,
            eval_every: int = 0, *, chunked: bool = True,
            donate: bool = True, prefetch: int = 0,
            max_chunk: int = 128, faults: Optional[FaultSchedule] = None,
            min_quorum: int = 1, checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0, resume: bool = False,
            consume: bool = False) -> Tuple[DiLoCoState, Dict]:
        """data_fn(step) -> per-worker-stacked batch pytree.

        ``chunked`` selects the scan-fused hot path (see module docstring);
        ``donate`` donates state buffers to the chunk/outer jits (the
        caller's state is copied once first, so it survives the run,
        unless ``consume`` hands it over as is: no copy, half the peak
        state memory, and the passed-in state is dead afterwards);
        ``prefetch`` > 0 assembles batches that many steps ahead on a
        background thread; ``max_chunk`` caps the scanned chunk length —
        ending a chunk early is always safe (between events ``after_step``
        is pure bookkeeping), and the cap bounds the on-device footprint
        of the stacked chunk batches for event-free strategies like DDP
        (0 = only events/evals/num_steps bound it; the default covers the
        paper's H=100 rounds in one chunk).

        Fault tolerance: ``faults`` scripts per-worker crash/rejoin/slow/
        drop/corrupt events and process-level kills (``repro.core.faults``);
        rounds proceed with the surviving subset while at least
        ``min_quorum`` workers contribute, and are skipped (workers keep
        training locally) below it.  ``checkpoint_dir`` + ``checkpoint_every``
        write crash-consistent outer-boundary checkpoints; ``resume=True``
        restores the latest one (state, runner extras, history, data cursor)
        and continues bit-exactly vs an uninterrupted run.
        """
        if not chunked:
            if prefetch > 0:
                raise ValueError(
                    "prefetch requires the chunked loop (chunked=True): "
                    "the per-step reference loop assembles batches "
                    "synchronously and would silently ignore it")
            if (faults is not None and not faults.empty) or checkpoint_dir \
                    or resume:
                raise ValueError(
                    "fault injection / checkpointing / resume require the "
                    "chunked loop (chunked=True): the per-step reference "
                    "loop has no chunk boundaries to anchor them to")
            # donate/max_chunk don't apply either: the reference loop
            # never donates and has no chunks
            return self._run_per_step(state, data_fn, num_steps,
                                      record_every, eval_fn, eval_every)
        if resume and not checkpoint_dir:
            raise ValueError("resume=True requires checkpoint_dir")
        eng = self.engine()
        runner = self.strategy.bind(eng, state.global_params, donate=donate)
        inner_chunk = jax.jit(eng.inner_chunk,
                              donate_argnums=(0,) if donate else ())
        tracker = None
        if faults is not None and not faults.empty:
            faults.validate(self.cfg.num_workers)
            tracker = FleetTracker(faults, self.cfg.num_workers,
                                   min_quorum=min_quorum)
            if faults.worker_events():
                # raises for runners that don't support per-worker faults;
                # kill-only schedules keep the whole fleet live, so the
                # runner needs no masks
                runner.bind_faults(tracker)
        if donate and not consume:
            # the first chunk donates the caller's state buffers; copy once
            # so the object the caller passed in survives the run
            state = jax.tree.map(jnp.copy, state)

        restored_history: Dict[str, list] = {}
        start_step = 0
        if resume:
            from repro.checkpoint import (latest_run_checkpoint,
                                          load_run_checkpoint)
            manifest = latest_run_checkpoint(checkpoint_dir)
            if manifest is not None:
                template = runner.checkpoint_extras()
                extras_template = template[0] if template is not None else None
                state, extras = load_run_checkpoint(manifest, state,
                                                    extras_template)
                runner.load_extras(extras,
                                   manifest.get("extras_meta") or {})
                restored_history = manifest.get("history") or {}
                start_step = int(manifest["step"])
                if tracker is not None:
                    tracker.catch_up(start_step)

        from repro.data.pipeline import Prefetcher, stack_batches
        source = (Prefetcher(data_fn, num_steps, depth=prefetch,
                             start=start_step)
                  if prefetch > 0 else None)

        history: Dict[str, list] = {"step": [], "loss": [], "sync_steps": [],
                                    "frag_syncs": [], "evals": []}
        for key, vals in restored_history.items():
            history[key] = [_history_from_json(v) for v in vals]

        def record(recs):
            for key, val in recs:
                # runners may emit novel keys (e.g. gossip_syncs): history
                # lists are created on demand
                history.setdefault(key, []).append(val)

        chunk_step_seconds = []
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATION_WARNING)
            def chunk_end(step: int) -> int:
                end = num_steps - 1
                event = runner.next_event(step)
                if event is not None:
                    end = min(end, max(event, step))
                if eval_fn is not None and eval_every:
                    # an eval landing mid-chunk splits the chunk (the
                    # eval must see the state at exactly that step)
                    end = min(end, (step // eval_every + 1) * eval_every - 1)
                if max_chunk:
                    end = min(end, step + max_chunk - 1)
                if checkpoint_dir and checkpoint_every:
                    # a checkpoint landing mid-chunk splits the chunk (the
                    # snapshot must see the state at exactly that boundary)
                    end = min(end, (step // checkpoint_every + 1)
                              * checkpoint_every - 1)
                if tracker is not None:
                    lim = tracker.chunk_limit(step)
                    if lim is not None:
                        end = min(end, max(lim, step))
                return end

            try:
                step = start_step
                chunk_index = 0
                t_prev = time.time()
                pending_ckpt = False
                while step < num_steps:
                    with jax.profiler.StepTraceAnnotation(
                            "train", step_num=chunk_index):
                        live = None
                        if tracker is not None:
                            live, recs = tracker.begin_chunk(step)
                            record(recs)
                        end = chunk_end(step)
                        T = end - step + 1
                        with _span("trainer.data"):
                            batches = (source.take(step, T)
                                       if source is not None
                                       else stack_batches(
                                           [data_fn(s)
                                            for s in range(step, end + 1)]))
                        if live is not None and all(live):
                            live = None
                        with _span("trainer.chunk", first_step=step,
                                   steps=T):
                            # a live mask only while a worker is down (its
                            # rows freeze); otherwise the whole-fleet trace
                            state, losses = inner_chunk(
                                state, batches,
                                *(() if live is None
                                  else (jnp.asarray(live, jnp.bool_),)))
                        with _span("trainer.fetch"):
                            losses_host = _fetch(losses)  # ONE per chunk
                        with _span("trainer.sync"):
                            for i in range(T):
                                s = step + i
                                loss_mean = _host_mean(losses_host[i],
                                                       live)
                                if s % record_every == 0:
                                    history["step"].append(s)
                                    history["loss"].append(loss_mean)
                                new_state, recs = runner.after_step(
                                    state, s, loss_mean)
                                if new_state is not state and i != T - 1:
                                    raise RuntimeError(
                                        f"sync runner replaced the state "
                                        f"at step {s}, mid-chunk (chunk "
                                        f"ends at {end}): next_event() "
                                        f"must report every step whose "
                                        f"after_step touches device state "
                                        f"— e.g. an HSchedule that fires "
                                        f"before since_sync reaches "
                                        f"current_h violates the chunked "
                                        f"contract; run with "
                                        f"chunked=False for such "
                                        f"schedules")
                                state = new_state
                                record(recs)
                        if source is not None and end + 1 < num_steps:
                            # the replay above just dispatched any outer
                            # sync asynchronously; start assembling the
                            # NEXT chunk's batches now so the stack +
                            # device_put overlap the sync instead of
                            # serializing behind it at the top of the
                            # loop.  next_event is accurate here (the
                            # runner replayed through ``end``), so the
                            # primed bounds match the next take(); if a
                            # custom runner shifts them anyway, take()
                            # falls back losslessly.
                            with _span("trainer.data"):
                                source.prime(end + 1,
                                             chunk_end(end + 1) - end)
                        t_now = time.time()
                        chunk_step_seconds.append((t_now - t_prev) / T)
                        t_prev = t_now
                        if checkpoint_dir and checkpoint_every and (
                                pending_ckpt
                                or (end + 1) % checkpoint_every == 0):
                            extras = runner.checkpoint_extras()
                            if extras is None:
                                # runner mid-round: its in-flight device
                                # state isn't serializable — defer to the
                                # next clean chunk boundary
                                pending_ckpt = True
                            else:
                                pending_ckpt = False
                                from repro.checkpoint import (
                                    save_run_checkpoint)
                                arrays, extras_meta = extras
                                with _span("trainer.checkpoint"):
                                    save_run_checkpoint(
                                        checkpoint_dir, end + 1,
                                        _fetch(state),
                                        extras_arrays=_fetch(arrays),
                                        extras_meta=extras_meta,
                                        history=history,
                                        meta={"num_steps": num_steps})
                                t_prev = time.time()  # ckpt IO != step time
                        if tracker is not None and tracker.kill_at(end):
                            # scripted process death: any due checkpoint
                            # was just written; the finally below closes
                            # the source and finalize() never runs —
                            # exactly a crash
                            raise SimulatedCrash(
                                f"scripted kill after step {end}")
                        if (eval_fn is not None and eval_every
                                and (end + 1) % eval_every == 0):
                            with _span("trainer.eval"):
                                state = runner.refresh(state)
                                history["evals"].append(
                                    (end, eval_fn(state.global_params)))
                            t_prev = time.time()    # eval time != step time
                    step = end + 1
                    chunk_index += 1
            finally:
                if source is not None:
                    source.close()
            state, recs = runner.finalize(state, num_steps)
            record(recs)
        # measured steady-state seconds/step: median over per-chunk means is
        # robust to the jit-compile spikes on first-seen chunk lengths
        history["step_seconds"] = sorted(chunk_step_seconds)[
            len(chunk_step_seconds) // 2] if chunk_step_seconds else 0.0
        return state, history

    def _run_per_step(self, state: DiLoCoState, data_fn, num_steps: int,
                      record_every: int = 1,
                      eval_fn: Optional[Callable] = None,
                      eval_every: int = 0) -> Tuple[DiLoCoState, Dict]:
        """The original per-step loop: one dispatch + one host sync per
        inner step.  Kept as the reference for the chunked path's
        bit-exactness tests and as the benchmark baseline.  Binds with
        donate=False — the pre-chunking loop never donated, and an
        eval_fn here may retain references into the live state."""
        eng = self.engine()
        runner = self.strategy.bind(eng, state.global_params, donate=False)
        inner_jit = jax.jit(eng.inner_step)
        history: Dict[str, list] = {"step": [], "loss": [], "sync_steps": [],
                                    "frag_syncs": [], "evals": []}

        def record(recs):
            for key, val in recs:
                # runners may emit novel keys (e.g. gossip_syncs): history
                # lists are created on demand
                history.setdefault(key, []).append(val)

        step_durations = []
        t_prev = time.time()
        for step in range(num_steps):
            state, loss, _ = inner_jit(state, data_fn(step))
            # host-side fixed-order mean of the raw per-worker losses —
            # bit-identical to the chunked loop's recording (_host_mean)
            loss_mean = _host_mean(_fetch(loss))
            if step % record_every == 0:
                history["step"].append(step)
                history["loss"].append(loss_mean)
            state, recs = runner.after_step(state, step, loss_mean)
            record(recs)
            # loss_mean + after_step forced this step (and any sync it
            # triggered) to complete before the clock is read
            t_now = time.time()
            step_durations.append(t_now - t_prev)
            t_prev = t_now
            if eval_fn is not None and eval_every and (step + 1) % eval_every == 0:
                state = runner.refresh(state)
                history["evals"].append((step, eval_fn(state.global_params)))
        state, recs = runner.finalize(state, num_steps)
        record(recs)
        # measured steady-state seconds/step: the median is robust to the
        # one-off jit-compile spikes (inner step at 0, outer step at the
        # first sync) that a mean over a short run would smear in
        history["step_seconds"] = sorted(step_durations)[
            len(step_durations) // 2] if step_durations else 0.0
        return state, history

    # -- communication accounting -------------------------------------------
    def payload_schedule(self, params, num_steps: int) -> list:
        """The strategy's payload footprint for ``num_steps`` inner steps —
        feed to ``repro.launch.comm_sim.simulate_schedule`` for modeled
        wall-clock."""
        n = sum(int(x.size) for x in jax.tree.leaves(params))
        return self.strategy.payload_schedule(n, num_steps, self.cfg)
