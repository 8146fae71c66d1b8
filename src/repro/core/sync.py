"""Pluggable synchronization strategies for the unified ``DistTrainer`` loop.

The paper frames DiLoCo as a lightweight wrapper over nanochat's training
loop; this module makes that literal.  One host-side loop (``DistTrainer``
in ``repro.core.dist_trainer``) drives vmapped inner steps, and a
``SyncStrategy`` decides everything cross-worker:

* ``DDPSync``        — synchronize every step (K=1 + the global batch, the
                       paper's "Standard DDP" baseline),
* ``CompressedDDPSync`` — K workers exchanging their per-step parameter
                       updates through a lossy codec (int8/fp8) with
                       error-feedback residuals held by the runner; with a
                       lossless codec it IS per-step delta-averaged DDP,

* ``DiLoCoSync``     — full delta exchange every H steps (paper §2.2),
                       pluggable H schedule incl. ``AdaptiveH``,
* ``StreamingSync``  — fragment-wise staggered exchange every H/F steps
                       (Streaming DiLoCo, arXiv:2501.18512),
* ``OverlappedSync`` — Streaming DiLoCo's "overlapping communication":
                       the delta is captured at step *t* but the outer
                       update lands at *t+delay*, hiding the exchange
                       behind inner compute; per-worker H jitter emulates
                       asynchronous / straggler workers (the delta of a
                       straggler reflects fewer inner steps),
* ``PipelinedSync``  — the DiLoCoX shape (arXiv:2506.21263): ONE fragment
                       per outer round, captured at the boundary and
                       applied ``delay`` steps later.  Each parameter syncs
                       every F·H steps, so combined with the int8 codec the
                       boundary traffic drops another ~4F× below f32
                       DiLoCo at unchanged compute,
* ``GossipSync``     — NoLoCo-style no-all-reduce averaging
                       (arXiv:2506.10911): each outer round every worker
                       averages its delta with ONE peer drawn from a
                       deterministic topology schedule (ring / random
                       matching / full), so per-worker sync traffic is
                       O(1) in fleet size.  Workers keep their own anchor
                       + outer momentum; K=2 (any pairing) and the full
                       topology are bit-exact ``DiLoCoSync``,
* ``AsyncGossipSync``— gossip where workers sync on their OWN step clocks
                       (per-worker period H+jitter_i) and the apply rule
                       drops or drift-reweights peer contributions staler
                       than ``staleness_bound`` inner steps.  With
                       jitter=0 and bound=0 it is bit-exact ``GossipSync``
                       (the synchronous barrier).

A strategy has two faces:

1. ``bind(engine, params) -> SyncRunner`` — a per-run state machine the
   training loop calls after every inner step;
2. ``payload_schedule(n_params, num_steps, cfg) -> [SyncEvent]`` — the pure
   communication footprint, consumed by the event-driven wall-clock
   simulator in ``repro.launch.comm_sim``.

The ``SyncRunner`` contract (what ``DistTrainer`` drives)
---------------------------------------------------------
* ``after_step(state, step, loss) -> (state, records)`` — called after
  EVERY inner step.  Between events it must be pure host bookkeeping that
  ignores ``state`` (under chunking it sees the post-chunk state for every
  step of the chunk); at an event it may run jitted device work and must
  return the replaced state.  ``records`` are ``(history_key, value)``
  pairs appended to the run history — any key is allowed, the trainer
  creates history lists on demand.
* ``next_event(step) -> Optional[int]`` — the first step >= ``step``
  whose ``after_step`` may touch device state.  The chunked loop scans
  inner steps to exactly that boundary in ONE device dispatch, so an
  under-reported event (firing mid-chunk) is a contract violation the
  trainer raises on.  ``None`` = no event before the run ends.
* ``refresh(state) -> state`` — bring ``global_params`` up to date for an
  observer (eval hook); identity for strategies that maintain it at every
  sync.
* ``finalize(state, num_steps) -> (state, records)`` — called once after
  the last step; flushes trailing partial rounds / in-flight applies so
  ``global_params`` reflects all work.
* donation (PR 4 rules): when bound with ``donate=True`` the runner's
  jits donate their state/residual/anchor arguments — call them as
  ``state, self.x = self._jit(state, self.x)`` so stale host references
  never outlive donated buffers, and any snapshot kept across steps must
  be a FRESH buffer (``jax.tree.map(jnp.copy, ...)``), never an alias of
  ``state`` leaves.

Per-worker byte accounting (``hop_bytes_per_worker``)
-----------------------------------------------------
``payload_schedule`` denominates ``SyncEvent.bytes_per_worker`` in bytes
each worker actually moves over ITS boundary link for one hop:

* codec'd delta exchange (DiLoCo family): per-worker scales make
  in-network reduction impossible, so the replicate hop is an all-GATHER
  — (K-1)·payload per worker, growing with fleet size;
* f32 DDP gradients are summable: bandwidth-optimal ring all-reduce,
  2·(K-1)/K·payload ≈ 2·payload;
* gossip: ONE peer payload per worker, flat in K (full topology is the
  gather again — it IS the DiLoCo mean).

Transport-layer contract (see ``repro.core.transport`` for the wire format)
---------------------------------------------------------------------------
Strategies never ship raw f32 pytrees.  Every exchange goes delta ->
``Codec.encode`` -> ``OuterPayload`` (wire-dtype data + per-tensor scales)
-> ``Transport.ship`` (the replicate hop, narrow dtype on the wire) ->
``Codec.decode`` -> averaged f32 — that path is
``outer_opt.exchange_and_average``, which every engine outer step calls.
Runners own the codec's per-worker error-feedback residual (created by
``engine.init_residual``; None for lossless codecs), thread it through
each ``*_ef`` outer step, and the payload schedules report bytes in the
codec's wire width with the codec name stamped on each ``SyncEvent`` so
the simulator can account bytes per codec.

Adding a new sync variant means implementing those two methods (~50 lines),
not writing a new training loop.
"""
from __future__ import annotations

import dataclasses
import functools
import random as _pyrandom
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DiLoCoConfig
from repro.core import outer_opt
from repro.core.schedule import FixedH, HSchedule
from repro.core.transport import make_codec

# history records a runner can emit: (history_key, value) pairs
Records = List[Tuple[str, Any]]

# the runners' deliberate device->host read for rejoin drift metrics (one
# tiny scalar pair per rejoin EVENT, never per step) — module-level so the
# host-sync lint pass recognizes the documented fetch point
_fetch = jax.device_get


@dataclasses.dataclass(frozen=True)
class SyncEvent:
    """One cross-worker payload on the slow (inter-pod) boundary.

    ``step`` is the inner step after which the payload leaves the worker;
    ``apply_step`` is the step by which the result must have landed (equal
    to ``step`` for blocking strategies, later for overlapped ones — the
    gap is the window the transfer may hide behind compute).  ``codec``
    names the wire codec so the simulator can account bytes per codec.
    """
    step: int
    bytes_per_worker: int
    kind: str                   # "grads" | "delta" | "fragment"
    apply_step: int
    fragment: int = -1
    codec: str = "f32"


def hop_bytes_per_worker(payload_bytes: int, k: int, collective: str) -> int:
    """Bytes ONE worker moves over its boundary link for one sync hop.

    ``collective`` names what the hop actually is on the wire:

    * ``"gather"`` — codec'd payloads carry per-worker scales, so rows
      cannot be summed in-network; every worker receives the other K-1
      rows: (K-1)·payload (K=1 degenerates to 1·payload);
    * ``"reduce"`` — summable f32 tensors (DDP grads): bandwidth-optimal
      ring all-reduce, 2·(K-1)/K·payload;
    * ``"peer"``   — gossip: one peer payload, flat in K.
    """
    if collective == "gather":
        return payload_bytes * max(k - 1, 1)
    if collective == "reduce":
        if k <= 1:
            return payload_bytes
        return int(payload_bytes * 2 * (k - 1) / k)
    if collective == "peer":
        return payload_bytes
    raise ValueError(f"unknown collective {collective!r}; "
                     "expected gather | reduce | peer")


@functools.lru_cache(maxsize=1)
def _jit_rejoin_drift():
    """Jitted per-rejoiner drift probe: ``(state, live, w)`` -> (L2 norm of
    worker w's delta from the anchor, cosine of that delta against the
    live fleet's mean delta).  Fixed signature — ``live`` and ``w`` are
    traced, so rejoin events never retrace.  Called on the PRE-adoption
    state, so it measures exactly the divergence the rejoin erases."""
    from repro.core.drift import delta_cosine

    def impl(state, live, w):
        delta = jax.tree.map(
            lambda wp, g: wp.astype(jnp.float32) - g.astype(jnp.float32)[None],
            state.worker_params, state.global_params)
        dw = jax.tree.map(lambda d: d[w], delta)
        lf = live.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(lf), 1.0)
        dmean = jax.tree.map(
            lambda d: jnp.tensordot(lf, d, axes=(0, 0)) / n, delta)
        norm = jnp.sqrt(outer_opt._tree_dot(dw, dw))
        return norm, delta_cosine(dw, dmean)

    return jax.jit(impl)


def _masks(info) -> Tuple[jax.Array, ...]:
    """The (contrib, adopt, reset) arguments of a quorum round, or none at
    all without one, so the programs trace their whole-fleet defaults."""
    if info is None:
        return ()
    return tuple(jnp.asarray(m) for m in (info.contrib, info.adopt,
                                          info.reset))


def _rejoin_drift_records(state, reset, live, step: int) -> Records:
    """``core.drift`` metrics for each rejoiner, recorded as
    ``("rejoin_drift", (step, worker, delta_norm, cos_to_live_mean))``."""
    recs: Records = []
    probe = _jit_rejoin_drift()
    live_arr = jnp.asarray(live)
    for w, r in enumerate(reset):
        if r:
            norm, cos = _fetch(probe(state, live_arr, jnp.int32(w)))
            recs.append(("rejoin_drift", (step, w, float(norm), float(cos))))
    return recs


class SyncRunner:
    """Per-run host-side state machine created by ``SyncStrategy.bind``.

    The chunked ``DistTrainer`` loop (``core.dist_trainer``) scans inner
    steps on device until the runner's next *event* — a step whose
    ``after_step`` touches device state (sync, snapshot, delayed apply).
    Between events ``after_step`` must be pure host bookkeeping (counters,
    loss windows) that ignores ``state``, because under chunking it is
    called with the post-chunk state for every step of the chunk.  When
    bound with ``donate=True`` the runner jits donate their
    state/residual arguments (params and momenta update in place), so any
    snapshot a runner keeps across steps must be a fresh buffer, never an
    alias of ``state`` leaves.
    """

    def after_step(self, state, step: int, loss: float):
        """Called after every inner step; returns (state, records)."""
        return state, []

    def next_event(self, step: int) -> Optional[int]:
        """First step >= ``step`` whose ``after_step`` may touch device
        state; ``None`` = no event before the run ends.  The base class is
        maximally conservative (every step is an event), which degrades
        the chunked loop to per-step execution."""
        return step

    def refresh(self, state):
        """Bring ``global_params`` up to date for an observer (eval hook);
        identity for strategies that maintain it on every sync."""
        return state

    def finalize(self, state, num_steps: int):
        """Called once after the last step; returns (state, records)."""
        return state, []

    # -- fault tolerance (quorum rounds + elastic rejoin) --------------------
    # Runners that understand per-worker fault events (crash / rejoin /
    # dropped payload) set ``supports_faults`` and accept a
    # ``core.faults.FleetTracker`` via ``bind_faults``; the trainer rejects
    # worker-level fault schedules for runners that do not.  Run-level
    # ``kill`` events (the crash/resume anchor) need no runner support.
    supports_faults = False
    _tracker = None

    def bind_faults(self, tracker) -> None:
        raise ValueError(
            f"{type(self).__name__} does not support per-worker fault "
            "injection (quorum sync / elastic rejoin); use one of the "
            "fault-aware strategies (diloco / ddp_compressed / streaming "
            "/ pipelined / gossip), or restrict the schedule to run-level "
            "kill/slow events")

    def _round_masks(self, state, step: int):
        """The bound tracker's ``RoundInfo`` for the round at ``step`` and
        its records, rejoin drift included; ``(None, [])`` with no tracker
        bound, where every program runs with its whole-fleet defaults."""
        if self._tracker is None:
            return None, []
        info = self._tracker.round_masks(step)
        records = list(info.records)
        if any(info.reset):
            records += _rejoin_drift_records(state, info.reset, info.live,
                                             step)
        return info, records

    def _adopt_skipped(self, state, info):
        """A round below quorum: no exchange, but rejoiners still adopt
        the current anchor (``engine.adopt_anchor``)."""
        if any(info.reset):
            state, self.residual = self._adopt(state, self.residual,
                                               jnp.asarray(info.reset))
        return state

    # -- crash-consistent checkpointing --------------------------------------
    def checkpoint_extras(self) -> Optional[Tuple[Any, Dict]]:
        """The runner-private state a resume needs: ``(arrays, meta)``
        where ``arrays`` is a pytree of device/host arrays (EF residuals,
        gossip anchors, ...) and ``meta`` is JSON-serializable host state
        (round counters, publish clocks).  Returns ``None`` when the
        runner is mid-round (e.g. a pipelined snapshot is in flight) and
        a checkpoint here would not be resumable — the trainer defers to
        the next clean chunk boundary.  The base runner is stateless, so
        any boundary is clean."""
        return {}, {}

    def load_extras(self, arrays, meta: Dict) -> None:
        """Restore what ``checkpoint_extras`` captured.  ``arrays`` is
        None when the checkpoint carried no array extras."""
        return None


class SyncStrategy:
    name = "base"

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        """Create the per-run state machine.  ``donate`` controls whether
        the runner's outer-step jits donate their state/residual
        arguments (``DistTrainer.run`` threads its own ``donate`` flag
        here; the per-step reference loop passes False to keep the
        pre-chunking no-donation behaviour)."""
        raise NotImplementedError

    def payload_schedule(self, n_params: int, num_steps: int,
                         cfg: DiLoCoConfig) -> List[SyncEvent]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# DDP — synchronize every step
# ---------------------------------------------------------------------------

class _DDPRunner(SyncRunner):
    def after_step(self, state, step, loss):
        # K=1 + global batch: the worker IS the global model, synchronized
        # by construction — nothing to exchange, just record the cadence.
        return state, [("sync_steps", step)]

    def next_event(self, step):
        return None     # never touches device state between refreshes

    def refresh(self, state):
        gp = jax.tree.map(lambda w: w[0], state.worker_params)
        return state._replace(global_params=gp)

    def finalize(self, state, num_steps):
        return self.refresh(state), []


@dataclasses.dataclass(frozen=True)
class DDPSync(SyncStrategy):
    """Fully synchronous baseline: fp32 gradient all-reduce every step."""
    name = "ddp"

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        if engine.cfg.num_workers != 1:
            raise ValueError(
                "DDPSync is the K=1 + global-batch baseline; "
                f"got num_workers={engine.cfg.num_workers}.  Use DiLoCoSync "
                "with H=1 for per-step delta averaging across workers.")
        return _DDPRunner()

    def payload_schedule(self, n_params, num_steps, cfg):
        # fp32 grads are summable: ring all-reduce, every step, blocking
        b = hop_bytes_per_worker(4 * n_params, cfg.num_workers, "reduce")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="grads",
                          apply_step=s) for s in range(num_steps)]


# ---------------------------------------------------------------------------
# Compressed DDP — per-step update exchange through a lossy codec
# ---------------------------------------------------------------------------

def compressed_ddp_config(cfg: DiLoCoConfig) -> DiLoCoConfig:
    """Fold ``cfg.grad_compress`` into a per-step delta-exchange config.

    H=1 with an identity outer update (lr=1, no momentum) makes the outer
    step exactly "average the workers' one-step parameter updates" — for
    SGD inner optimizers that is literally gradient averaging, and for
    AdamW/Muon it is DDP on the *effective update*, which is the quantity
    gradient-compression schemes actually care about.  The codec (and its
    error-feedback residual, held by the sync runner) then rides the same
    transport stack as every DiLoCo variant.
    """
    codec = cfg.grad_compress if cfg.grad_compress not in ("", "none") \
        else "float32"
    return dataclasses.replace(
        cfg, strategy="ddp_compressed", h_inner_steps=1, outer_lr=1.0,
        outer_momentum=0.0, nesterov=False, delta_dtype=codec)


@dataclasses.dataclass(frozen=True)
class CompressedDDPSync(SyncStrategy):
    """DDP with compressed per-step exchange: K workers average their
    one-step parameter updates through the configured codec every step.
    Build the config with ``compressed_ddp_config`` — the identity outer
    update (H=1, lr=1, mu=0) is what makes this DDP rather than DiLoCo;
    ``bind`` rejects configs that would silently change the semantics.
    Lossless codec => bitwise per-step delta-averaged DDP; int8/fp8 adds
    the quantizer + error feedback, the second anchor the benchmarks
    compare DiLoCo's bandwidth savings against."""
    name = "ddp_compressed"

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        cfg = engine.cfg
        if cfg.outer_lr != 1.0 or cfg.outer_momentum != 0.0 or cfg.nesterov:
            raise ValueError(
                "CompressedDDPSync needs the identity outer update "
                "(outer_lr=1, outer_momentum=0, nesterov=False) — build the "
                "config with sync.compressed_ddp_config(); got "
                f"lr={cfg.outer_lr} mu={cfg.outer_momentum} "
                f"nesterov={cfg.nesterov}")
        return _DiLoCoRunner(engine, params, FixedH(1), donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        codec = make_codec(cfg.delta_dtype if cfg.strategy == "ddp_compressed"
                           else (cfg.grad_compress
                                 if cfg.grad_compress not in ("", "none")
                                 else "float32"))
        b = hop_bytes_per_worker(codec.schedule_bytes(n_params),
                                 cfg.num_workers, "gather")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="grads",
                          apply_step=s, codec=codec.name)
                for s in range(num_steps)]


# ---------------------------------------------------------------------------
# DiLoCo — full delta exchange every H steps
# ---------------------------------------------------------------------------

class _DiLoCoRunner(SyncRunner):
    supports_faults = True

    def __init__(self, engine, params, hs: HSchedule, donate: bool = True):
        self.engine = engine
        self.hs = hs
        self.since = 0
        self.residual = engine.init_residual(params)
        self._donate = donate
        self._outer = jax.jit(engine.outer_step_ef,
                              donate_argnums=(0, 1) if donate else ())

    def bind_faults(self, tracker):
        self._tracker = tracker
        self._adopt = jax.jit(self.engine.adopt_anchor,
                              donate_argnums=(0, 1) if self._donate else ())

    def _sync(self, state, step):
        info, records = self._round_masks(state, step)
        if info is not None and info.skip:
            return self._adopt_skipped(state, info), records
        state, self.residual = self._outer(state, self.residual,
                                           *_masks(info))
        return state, records + [("sync_steps", step)]

    def after_step(self, state, step, loss):
        self.since += 1
        if self.hs.should_sync(step, self.since, loss):
            self.since = 0
            return self._sync(state, step)
        return state, []

    def finalize(self, state, num_steps):
        if self.since:  # trailing sync so global_params reflect all work
            return self._sync(state, num_steps - 1)
        return state, []

    def checkpoint_extras(self):
        if self.since:
            # mid-round: ``since`` (and AdaptiveH's loss window) are not
            # serialized — defer to the outer boundary, where both are
            # trivially zero/fresh
            return None
        return {"residual": self.residual}, {}

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.residual = arrays["residual"]

    def next_event(self, step):
        # syncs fire when since_sync reaches the schedule's current H, and
        # every supported HSchedule only changes H at a sync (AdaptiveH's
        # loss window is fed per step by after_step, but its slope check
        # runs at the boundary), so the next boundary is deterministic
        try:
            h = int(self.hs.current_h)
        except Exception:       # exotic schedule: degrade to per-step
            return step
        return step + max(h - self.since, 1) - 1


@dataclasses.dataclass(frozen=True)
class DiLoCoSync(SyncStrategy):
    """Paper §2.2: average parameter deltas + outer Nesterov SGD every H.

    ``h`` overrides the config's ``h_inner_steps``; ``h_schedule`` plugs in
    any ``HSchedule`` (e.g. ``AdaptiveH``) instead of fixed H.
    """
    name = "diloco"
    h: Optional[int] = None
    h_schedule: Optional[HSchedule] = None

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        hs = self.h_schedule or FixedH(self.h or engine.cfg.h_inner_steps)
        return _DiLoCoRunner(engine, params, hs, donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        h = self.h or cfg.h_inner_steps
        codec = make_codec(cfg.delta_dtype)
        b = hop_bytes_per_worker(codec.schedule_bytes(n_params),
                                 cfg.num_workers, "gather")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="delta",
                          apply_step=s, codec=codec.name)
                for s in range(h - 1, num_steps, h)]


# ---------------------------------------------------------------------------
# Streaming DiLoCo — one fragment every H/F steps, staggered
# ---------------------------------------------------------------------------

class _StreamingRunner(SyncRunner):
    supports_faults = True

    def __init__(self, engine, params, donate: bool = True):
        from repro.core.streaming import fragment_masks
        self.engine = engine
        self.F = engine.num_fragments
        self.masks = fragment_masks(params, self.F)
        self.period = engine.fragment_schedule()
        self.residual = engine.init_residual(params)
        self._donate = donate
        # donate state + residual (arg 1 is the reused fragment mask)
        self._frag = jax.jit(engine.outer_step_fragment_ef,
                             donate_argnums=(0, 2) if donate else ())

    def bind_faults(self, tracker):
        self._tracker = tracker
        self._adopt = jax.jit(self.engine.adopt_anchor,
                              donate_argnums=(0, 1) if self._donate else ())

    def after_step(self, state, step, loss):
        if (step + 1) % self.period:
            return state, []
        f = ((step + 1) // self.period - 1) % self.F
        info, records = self._round_masks(state, step)
        if info is not None and info.skip:
            return self._adopt_skipped(state, info), records
        state, self.residual = self._frag(state, self.masks[f],
                                          self.residual, *_masks(info))
        return state, records + [("frag_syncs", (step, f))]

    def checkpoint_extras(self):
        # the fragment slot is a pure function of the step index and
        # un-synced divergence lives entirely in the state, so every
        # chunk boundary is clean
        return {"residual": self.residual}, {}

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.residual = arrays["residual"]

    def next_event(self, step):
        # fragment boundaries: every step s with (s + 1) % period == 0
        return (step // self.period + 1) * self.period - 1


@dataclasses.dataclass(frozen=True)
class StreamingSync(SyncStrategy):
    """Fragment-wise staggered sync (arXiv:2501.18512): every parameter
    still syncs each H, but instantaneous bandwidth demand drops F×."""
    name = "streaming"
    num_fragments: int = 4

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        return _StreamingRunner(engine, params, donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        h = cfg.h_inner_steps
        period = max(h // self.num_fragments, 1)
        codec = make_codec(cfg.delta_dtype)
        b = hop_bytes_per_worker(
            codec.schedule_bytes(n_params // self.num_fragments),
            cfg.num_workers, "gather")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="fragment",
                          # a fragment may stream until its next slot
                          apply_step=s + period - 1,
                          fragment=((s + 1) // period - 1) % self.num_fragments,
                          codec=codec.name)
                for s in range(period - 1, num_steps, period)]


# ---------------------------------------------------------------------------
# Overlapped DiLoCo — delta captured at t, outer update applied at t+delay
# ---------------------------------------------------------------------------

class _OverlappedRunner(SyncRunner):
    """Captures per-worker delta snapshots (with straggler jitter) at each
    round boundary and applies the outer update ``delay`` steps later.
    Inner progress made during the communication window is carried forward:
    at apply time worker i becomes  new_global + (w_now_i − snap_i).
    With delay=0 and jitter=0 this is exactly ``DiLoCoSync``."""

    def __init__(self, engine, params, h: int, delay: int, jitter: int,
                 seed: int, donate: bool = True):
        if not 0 <= delay < h:
            raise ValueError(f"need 0 <= delay < h, got delay={delay} h={h}")
        if jitter < 0 or jitter + delay >= h:
            raise ValueError(
                f"need jitter + delay < h so every snapshot lands after the "
                f"previous apply, got jitter={jitter} delay={delay} h={h}")
        self.engine = engine
        self.h, self.delay, self.jitter = h, delay, jitter
        self.k = engine.cfg.num_workers
        self.seed = seed
        self.rng = _pyrandom.Random(seed)
        self.round_end = h - 1
        self.snap_steps = self._draw_snap_steps()
        self.buf = None                 # snapshot buffer being filled
        self.pending = None             # frozen snapshot awaiting apply
        self.pending_apply = -1
        self.residual = engine.init_residual(params)
        self._snap_row = jax.jit(
            lambda buf, wp, i: jax.tree.map(
                lambda b, w: b.at[i].set(w[i]), buf, wp))
        # donate state + residual; the snapshot is NOT donated — there is
        # no second (K, ...) output left to reuse its buffer for (the
        # worker-param output aliases the donated state's)
        self._apply = jax.jit(self._apply_impl,
                              donate_argnums=(0, 2) if donate else ())
        self._outer = jax.jit(engine.outer_step_ef,
                              donate_argnums=(0, 1) if donate else ())

    def _draw_snap_steps(self) -> Dict[int, int]:
        """Worker i's delta leaves jitter_i steps before the boundary — a
        straggler's contribution reflects fewer inner steps."""
        return {i: self.round_end
                - (self.rng.randint(0, self.jitter) if self.jitter else 0)
                for i in range(self.k)}

    def _apply_impl(self, state, snap, residual):
        cfg = self.engine.cfg
        delta = jax.tree.map(
            lambda s, g: s.astype(jnp.float32) - g.astype(jnp.float32)[None],
            snap, state.global_params)
        avg, new_res = outer_opt.exchange_and_average(
            delta, cfg, self.engine.replicate_fn, residual=residual)
        new_global, new_outer = outer_opt.outer_update(
            state.global_params, avg, state.outer, cfg)
        # carry forward the inner progress made while the exchange was in
        # flight: worker = synced base + (current − snapshot)
        new_wp = jax.tree.map(
            lambda w, s, ng: (ng.astype(jnp.float32)[None]
                              + (w.astype(jnp.float32) - s.astype(jnp.float32))
                              ).astype(w.dtype),
            state.worker_params, snap, new_global)
        return state._replace(global_params=new_global,
                              worker_params=new_wp, outer=new_outer), new_res

    def after_step(self, state, step, loss):
        records: Records = []
        due = [i for i, s in self.snap_steps.items() if s == step]
        if due:
            if self.jitter == 0:
                # every worker snaps at the boundary: one whole-tree copy
                # (fresh buffers — the donated chunk/apply jits recycle
                # the state's, so the snapshot must never alias them)
                self.buf = jax.tree.map(jnp.copy, state.worker_params)
            else:
                if self.buf is None:
                    self.buf = state.worker_params
                for i in due:
                    # .at[].set yields fresh buffers, so the finished buf
                    # never aliases donated state leaves either
                    self.buf = self._snap_row(self.buf, state.worker_params,
                                              jnp.int32(i))
        if step == self.round_end:
            # every worker's snap step is <= round_end and was processed
            # above, so buf is always populated here
            self.pending = self.buf
            self.pending_apply = step + self.delay
            self.buf = None
            self.round_end += self.h
            self.snap_steps = self._draw_snap_steps()
        if self.pending is not None and step >= self.pending_apply:
            state, self.residual = self._apply(state, self.pending,
                                               self.residual)
            self.pending = None
            records.append(("sync_steps", step))
        return state, records

    def next_event(self, step):
        cands = [s for s in self.snap_steps.values() if s >= step]
        cands.append(self.round_end)
        if self.pending is not None:
            cands.append(max(self.pending_apply, step))
        return min(cands)

    def finalize(self, state, num_steps):
        records: Records = []
        if self.pending is not None:  # flush the in-flight round
            state, self.residual = self._apply(state, self.pending,
                                               self.residual)
            self.pending = None
            records.append(("sync_steps", num_steps - 1))
        if num_steps % self.h:        # trailing partial round: full sync
            state, self.residual = self._outer(state, self.residual)
            records.append(("sync_steps", num_steps - 1))
        return state, records

    def checkpoint_extras(self):
        if self.pending is not None or self.buf is not None:
            return None     # snapshot in flight: defer to a clean boundary
        return {"residual": self.residual}, {"round_end": self.round_end}

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.residual = arrays["residual"]
        # replay the jitter draws so the RNG stream continues bit-exactly
        self.rng = _pyrandom.Random(self.seed)
        self.round_end = self.h - 1
        self.snap_steps = self._draw_snap_steps()
        while self.round_end < int(meta["round_end"]):
            self.round_end += self.h
            self.snap_steps = self._draw_snap_steps()


@dataclasses.dataclass(frozen=True)
class OverlappedSync(SyncStrategy):
    """Streaming DiLoCo's overlapping communication for the *full* delta:
    capture at t, apply at t+delay, with per-worker straggler jitter.

    ``seed`` makes the jitter draws reproducible; ``make_strategy`` threads
    ``DiLoCoConfig.sync_seed`` here."""
    name = "overlapped"
    h: Optional[int] = None
    delay: int = 0
    jitter: int = 0
    seed: int = 0

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        h = self.h or engine.cfg.h_inner_steps
        return _OverlappedRunner(engine, params, h, self.delay, self.jitter,
                                 self.seed, donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        h = self.h or cfg.h_inner_steps
        codec = make_codec(cfg.delta_dtype)
        b = hop_bytes_per_worker(codec.schedule_bytes(n_params),
                                 cfg.num_workers, "gather")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="delta",
                          apply_step=s + self.delay, codec=codec.name)
                for s in range(h - 1, num_steps, h)]


# ---------------------------------------------------------------------------
# Pipelined (DiLoCoX) — ONE quantized fragment per round, delayed apply
# ---------------------------------------------------------------------------

class _PipelinedRunner(SyncRunner):
    """One fragment per outer round: at each H boundary the round's
    fragment (round mod F) is snapshotted, its encoded delta crosses the
    boundary while inner compute continues, and the outer update lands
    ``delay`` steps later.  Worker progress made in flight is carried
    forward on the fragment slots (like ``_OverlappedRunner``); the other
    slots keep diverging until their round comes up.  With F=1, delay=0
    this is exactly ``DiLoCoSync``."""

    supports_faults = True

    def __init__(self, engine, params, h: int, delay: int,
                 num_fragments: int, donate: bool = True):
        if not 0 <= delay < h:
            raise ValueError(f"need 0 <= delay < h, got delay={delay} h={h}")
        from repro.core.streaming import fragment_masks
        self.engine = engine
        self.h, self.delay, self.F = h, delay, num_fragments
        self.masks = fragment_masks(params, num_fragments)
        self.residual = engine.init_residual(params)
        self.round = 0
        self.pending = None   # (snapshot, fragment, RoundInfo|None) in flight
        self.pending_apply = -1
        self._donate = donate
        self._apply = jax.jit(self._apply_impl, static_argnames=("frag",),
                              donate_argnums=(0, 2) if donate else ())
        self._outer = jax.jit(engine.outer_step_ef,
                              donate_argnums=(0, 1) if donate else ())

    def bind_faults(self, tracker):
        self._tracker = tracker
        self._adopt = jax.jit(self.engine.adopt_anchor,
                              donate_argnums=(0, 1) if self._donate else ())

    def _apply_impl(self, state, snap, residual, contrib=None, adopt=None,
                    reset=None, *, frag: int):
        """Land fragment ``frag``'s outer update, computed from the
        snapshot ``snap``.  Under (K,) quorum masks ``contrib`` rows enter
        the fragment's masked average, ``adopt`` rows take the synced
        fragment slots with in-flight carry-forward, ``reset`` rows
        (rejoiners) land on the FULL new global with zeroed inner-opt/EF
        state, and dead rows pass through frozen.  None is the whole
        fleet, folded away at compile time (``outer_opt.fleet_mask``)."""
        cfg = self.engine.cfg
        rows = outer_opt._mask_rows
        contrib = outer_opt.fleet_mask(contrib, cfg.num_workers, True)
        adopt = outer_opt.fleet_mask(adopt, cfg.num_workers, True)
        reset = outer_opt.fleet_mask(reset, cfg.num_workers, False)
        mask = self.masks[frag]
        delta = jax.tree.map(
            lambda s, g, m: (s.astype(jnp.float32)
                             - g.astype(jnp.float32)[None]) * m[None],
            snap, state.global_params, mask)
        res_in = residual if residual is None else jax.tree.map(
            lambda r, m: r * m[None], residual, mask)
        avg, new_res = outer_opt.exchange_and_average(
            delta, cfg, self.engine.replicate_fn, residual=res_in,
            kind="fragment", fragment=frag, live=contrib)
        new_global, new_outer = outer_opt.outer_update(
            state.global_params, avg, state.outer, cfg)
        new_global = jax.tree.map(
            lambda ng, g, m: jnp.where(m, ng, g),
            new_global, state.global_params, mask)
        # adopters' fragment slots: synced base + progress made while in
        # flight; their other slots untouched; rejoiners take every slot
        new_wp = jax.tree.map(
            lambda w, s, ng, m: jnp.where(
                rows(reset, w), ng[None].astype(w.dtype),
                jnp.where(
                    jnp.logical_and(rows(adopt, w), m[None]),
                    (ng.astype(jnp.float32)[None]
                     + (w.astype(jnp.float32) - s.astype(jnp.float32))
                     ).astype(w.dtype),
                    w)),
            state.worker_params, snap, new_global, mask)
        if residual is not None:
            new_res = outer_opt.zero_rows(reset, jax.tree.map(
                lambda nr, r, m: jnp.where(
                    jnp.logical_and(rows(contrib, r), m[None]), nr, r),
                new_res, residual, mask))
        return state._replace(
            global_params=new_global, worker_params=new_wp,
            inner_opt=outer_opt.zero_rows(reset, state.inner_opt),
            outer=new_outer), new_res

    def _apply_pending(self, state, step) -> Tuple[Any, Records]:
        snap, frag, info = self.pending
        self.pending = None
        if info is not None:
            if info.skip:
                return self._adopt_skipped(state, info), []
            # a worker that crashed while the snapshot was in flight must
            # not adopt the landing update: intersect with the live set
            info = dataclasses.replace(info, adopt=tuple(
                a and l for a, l in zip(info.adopt, self._tracker.live)))
        state, self.residual = self._apply(state, snap, self.residual,
                                           *_masks(info), frag=frag)
        return state, [("frag_syncs", (step, frag))]

    def after_step(self, state, step, loss):
        records: Records = []
        if (step + 1) % self.h == 0:
            # masks captured WITH the snapshot: the deltas in flight are
            # the capture-time live set's
            info, records = self._round_masks(state, step)
            # copy, not alias: the chunked loop (and the donated apply)
            # consume the state's buffers while this snapshot is in flight
            self.pending = (jax.tree.map(jnp.copy, state.worker_params),
                            self.round % self.F, info)
            self.pending_apply = step + self.delay
            self.round += 1
        if self.pending is not None and step >= self.pending_apply:
            state, recs = self._apply_pending(state, step)
            records += recs
        return state, records

    def next_event(self, step):
        cands = [(step // self.h + 1) * self.h - 1]   # next round boundary
        if self.pending is not None:
            cands.append(max(self.pending_apply, step))
        return min(cands)

    def finalize(self, state, num_steps):
        records: Records = []
        if self.pending is not None:  # flush the in-flight fragment
            state, recs = self._apply_pending(state, num_steps - 1)
            records += recs
        if num_steps % self.h:        # trailing partial round: full sync
            info = None
            if self._tracker is not None:
                info = self._tracker.round_masks(num_steps - 1)
                records += list(info.records)
            if info is None or not info.skip:
                state, self.residual = self._outer(state, self.residual,
                                                   *_masks(info))
                records.append(("sync_steps", num_steps - 1))
        return state, records

    def checkpoint_extras(self):
        if self.pending is not None:
            return None     # fragment in flight: defer to a clean boundary
        return {"residual": self.residual}, {"round": self.round}

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.residual = arrays["residual"]
        self.round = int(meta["round"])


@dataclasses.dataclass(frozen=True)
class PipelinedSync(SyncStrategy):
    """DiLoCoX-style pipelined low-bandwidth sync (arXiv:2506.21263): one
    fragment per outer round, overlapped with compute via ``delay``.  Each
    parameter syncs every F·H steps — combine with the int8 codec for the
    compounded ~4F× boundary-byte reduction over f32 DiLoCo."""
    name = "pipelined"
    h: Optional[int] = None
    num_fragments: int = 4
    delay: int = 0

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        h = self.h or engine.cfg.h_inner_steps
        return _PipelinedRunner(engine, params, h, self.delay,
                                self.num_fragments, donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        h = self.h or cfg.h_inner_steps
        codec = make_codec(cfg.delta_dtype)
        b = hop_bytes_per_worker(
            codec.schedule_bytes(n_params // self.num_fragments),
            cfg.num_workers, "gather")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="fragment",
                          apply_step=s + self.delay,
                          fragment=((s + 1) // h - 1) % self.num_fragments,
                          codec=codec.name)
                for s in range(h - 1, num_steps, h)]


# ---------------------------------------------------------------------------
# Gossip — no-all-reduce peer averaging (NoLoCo, arXiv:2506.10911)
# ---------------------------------------------------------------------------

GOSSIP_TOPOLOGIES = ("ring", "random", "full")


def _matching_from_order(order: List[int]) -> List[int]:
    """Pair consecutive entries of ``order`` into an involution: peer[i] is
    i's partner; an odd leftover is self-paired (a solo outer step)."""
    peer = list(range(len(order)))
    for a in range(0, len(order) - 1, 2):
        i, j = order[a], order[a + 1]
        peer[i], peer[j] = j, i
    return peer


def gossip_peers(k: int, round_idx: int, topology: str,
                 seed: int = 0) -> Optional[List[int]]:
    """The deterministic peer matching for one gossip round.

    Returns ``peer`` with ``peer[peer[i]] == i`` (an involution), or
    ``None`` for the full topology (average ALL workers — the DiLoCo
    mean).  ``ring`` alternates the pairing offset each round so
    information walks around the ring; ``random`` draws a fresh seeded
    matching per round (NoLoCo's schedule), keyed by ``(seed, round)`` so
    runs reproduce.
    """
    if topology == "full":
        return None
    if topology == "ring":
        off = round_idx % 2
        order = [(off + j) % k for j in range(k)]
    elif topology == "random":
        order = list(range(k))
        # int-keyed (tuple seeding is deprecated); still (seed, round)-unique
        _pyrandom.Random((seed << 32) ^ round_idx).shuffle(order)
    else:
        raise ValueError(f"unknown gossip topology {topology!r}; "
                         f"expected one of {GOSSIP_TOPOLOGIES}")
    return _matching_from_order(order)


@dataclasses.dataclass(frozen=True)
class GossipRound:
    """One gossip exchange for the event-driven simulator
    (``repro.launch.comm_sim.simulate_gossip``).

    ``emit_steps[w]`` is the (worker-local) step at which worker w ships
    its ``nbytes`` payload (-1 = w does not participate this round);
    ``deps[w]`` lists the ``(src_worker, src_emit_step)`` transfers w's
    apply consumes — a pair barrier for ring/random gossip, all K-1 peers
    for the full topology, empty when the contribution was dropped."""
    emit_steps: Tuple[int, ...]
    deps: Tuple[Tuple[Tuple[int, int], ...], ...]
    nbytes: int
    codec: str = "f32"


def _gossip_payload_bytes(codec, n_params: int) -> int:
    """One gossip publication on the wire: the codec'd delta PLUS the
    sender's f32 anchors and outer momentum (pair consensus averages the
    whole outer state — without it the receiver cannot mix, and the
    per-worker anchors random-walk apart; NoLoCo ships parameters for
    the same reason).  Still one flat peer payload, independent of fleet
    size — the all-reduce gather ships (K-1) of these."""
    return codec.schedule_bytes(n_params) + 2 * 4 * n_params


def _gossip_outer_rows(cfg, state, anchors, v, avg):
    """Per-row Nesterov outer update on stacked (K, ...) trees.  The math
    in ``outer_opt.outer_update`` is purely elementwise, so the stacked
    call IS the per-row update — no vmap needed, and the emitted code
    matches DiLoCoSync's unstacked call (pinned by the K=2 equivalence
    test)."""
    new_anchors, ostate = outer_opt.outer_update(
        anchors, avg, outer_opt.OuterState(v=v, t=state.outer.t), cfg)
    return new_anchors, ostate.v


def _gossip_land(state, anchors, v, residual, take, adopt, reset):
    """Where a gossip round (or a skipped round's rejoin) leaves the fleet.

    ``reset`` rows (rejoiners) adopt the ``adopt`` rows' anchor consensus
    with zeroed outer momentum, inner-opt and EF state; ``take`` and
    ``reset`` rows' params land on their anchors; ``global_params`` tracks
    the live fleet's anchor mean (dead anchors are stale), the consensus
    estimate eval / checkpoint consumers read.  Every mean is
    ``outer_opt.masked_mean``, which the whole-fleet masks fold to
    ``jnp.mean``.  Returns (state, anchors, v, residual)."""
    rows = outer_opt._mask_rows
    anchors = jax.tree.map(
        lambda a: jnp.where(
            rows(reset, a),
            outer_opt.masked_mean(adopt, a.astype(jnp.float32))[None]
            .astype(a.dtype), a),
        anchors)
    take = jnp.logical_or(take, reset)
    new_wp = jax.tree.map(
        lambda a, w: jnp.where(rows(take, w), a.astype(w.dtype), w),
        anchors, state.worker_params)
    live = jnp.logical_or(adopt, reset)
    new_global = jax.tree.map(
        lambda a, g: outer_opt.masked_mean(
            live, a.astype(jnp.float32)).astype(g.dtype),
        anchors, state.global_params)
    if residual is not None:
        residual = outer_opt.zero_rows(reset, residual)
    return (state._replace(
        global_params=new_global, worker_params=new_wp,
        inner_opt=outer_opt.zero_rows(reset, state.inner_opt)),
        anchors, outer_opt.zero_rows(reset, v), residual)


def _gossip_pair_impl(cfg, replicate_fn, state, anchors, v, residual,
                      peer_idx, active=None, adopt=None, reset=None):
    """One synchronized gossip round: encode per-worker deltas, ship ONE
    peer row each, pair-average, per-row outer update.

    The (K,) fleet masks of a quorum round (traced, so a changing live set
    never retraces):

    * ``active`` — contributors, pair-matched among themselves (a solo
      leftover self-pairs: its pair mean is the identity, a solo outer
      step);
    * ``adopt``  — live veterans, whose post-round anchor mean is the
      consensus estimate a rejoiner adopts;
    * ``reset``  — rejoiners: anchors/params := consensus, outer momentum,
      inner-opt and EF state := 0;
    * rows in none of the masks (dead workers) pass through frozen.

    None is the whole fleet, folded away at compile time
    (``outer_opt.fleet_mask``).

    Module-level on purpose: ``GossipSync`` and the fully-synchronous
    ``AsyncGossipSync`` specialization jit THIS SAME function, so bitwise
    equality between them is structural (one traced module), not a
    compiler accident — XLA:CPU contracts mul+add chains to FMAs per
    module at the LLVM level, below HLO, so even ``optimization_barrier``
    cannot pin cross-module rounding."""
    k = cfg.num_workers
    rows = outer_opt._mask_rows
    active = outer_opt.fleet_mask(active, k, True)
    adopt = outer_opt.fleet_mask(adopt, k, True)
    reset = outer_opt.fleet_mask(reset, k, False)
    transport = outer_opt.make_transport(cfg, replicate_fn)
    delta = jax.tree.map(
        lambda w, a: w.astype(jnp.float32) - a.astype(jnp.float32),
        state.worker_params, anchors)
    dq, peer_dq, new_res = transport.exchange_peers(delta, peer_idx,
                                                    residual)
    # pair CONSENSUS: the pair averages its whole OUTER STATE — anchors
    # and outer momentum — not just its deltas (NoLoCo ships parameters
    # for the same reason).  Delta-only averaging leaves the per-worker
    # anchors on an uncontracted random walk and the fleet never agrees;
    # unmixed momentum keeps amplifying per-worker disagreement.  The
    # matching is an involution, so both pair members compute the same
    # mix and land on IDENTICAL outer state; x*0.5 is exact, so with
    # equal rows (K=2) the mix is bitwise a no-op and the 2-row mean is
    # bitwise the DiLoCo mean (a+b)/2.
    def pair_mean(t):
        peer_rows = jax.tree.map(lambda x: x[peer_idx], t)
        return jax.tree.map(lambda a, b: a * 0.5 + b * 0.5, t, peer_rows)

    base, v_mix = pair_mean(anchors), pair_mean(v)
    avg = jax.tree.map(lambda a, b: a * 0.5 + b * 0.5, dq, peer_dq)
    cand_anchors, cand_v = _gossip_outer_rows(cfg, state, base, v_mix, avg)

    def merge(n, o):
        return jnp.where(rows(active, n), n, o)

    new_anchors = jax.tree.map(merge, cand_anchors, anchors)
    new_v = jax.tree.map(merge, cand_v, v)
    if new_res is not None:
        new_res = jax.tree.map(merge, new_res, residual)
    new_state, new_anchors, new_v, new_res = _gossip_land(
        state, new_anchors, new_v, new_res, active, adopt, reset)
    return (new_state._replace(outer=outer_opt.OuterState(
        state.outer.v, state.outer.t + 1)), new_anchors, new_v, new_res)


def _gossip_async_impl(cfg, replicate_fn, state, anchors, v, residual, pub,
                       pub_anch, pub_v, due, peer, base_w, gate):
    """One async-gossip apply event with a dynamic due-set.

    ``due``/``peer``/``base_w``/``gate`` are (K,) arrays — the jit
    signature is fixed, so a changing due-set or matching never
    retraces.  All rows are encoded in one fixed-shape pass; non-due rows
    (params, momentum, EF residual, published delta) are masked back to
    their previous values, so a worker that shipped nothing advances
    nothing."""
    from repro.core.drift import delta_cosine
    transport = outer_opt.make_transport(cfg, replicate_fn)

    def rows(m, a):      # (K,) mask/weight -> broadcast over a row tree
        return m.reshape((-1,) + (1,) * (a.ndim - 1))

    delta = jax.tree.map(
        lambda w, a: w.astype(jnp.float32) - a.astype(jnp.float32),
        state.worker_params, anchors)
    dq, new_res = transport.exchange(delta, residual)

    def publish(new, old):
        return jax.tree.map(
            lambda n, o: jnp.where(rows(due, n), n, o), new, old)

    # a publication is (delta, anchors, momentum)-at-publish: the
    # consumer mixes the whole outer state, so the pair-consensus
    # contraction survives the missing barrier
    pub_new = publish(dq, pub)
    pub_anch_new = publish(anchors, pub_anch)
    pub_v_new = publish(v, pub_v)
    peer_dq = jax.tree.map(lambda p: p[peer], pub_new)
    # observed drift: a stale peer delta pointing away from the local one
    # is down-weighted toward zero (gate is set only for 0 < s <= bound)
    cos = jax.vmap(delta_cosine)(dq, peer_dq)                        # (K,)
    w_eff = jnp.where(gate, base_w * jnp.maximum(cos, 0.0), base_w)

    def mix(own, published):
        peer_rows = jax.tree.map(lambda p: p[peer], published)
        return jax.tree.map(
            lambda a, b: a * rows(1.0 - w_eff, a) + b * rows(w_eff, b),
            own, peer_rows)

    avg = jax.tree.map(
        lambda a, b: a * rows(1.0 - w_eff, a) + b * rows(w_eff, b),
        dq, peer_dq)
    base = mix(anchors, pub_anch_new)
    v_mix = mix(v, pub_v_new)
    cand_anchors, cand_v = _gossip_outer_rows(cfg, state, base, v_mix, avg)

    def merge(n, o):
        return jnp.where(rows(due, n), n, o)

    new_anchors = jax.tree.map(merge, cand_anchors, anchors)
    new_v = jax.tree.map(merge, cand_v, v)
    new_wp = jax.tree.map(
        lambda a, wp: jnp.where(rows(due, wp), a.astype(wp.dtype), wp),
        new_anchors, state.worker_params)
    if residual is not None:
        new_res = jax.tree.map(merge, new_res, residual)
    new_global = jax.tree.map(
        lambda a, g: jnp.mean(a.astype(jnp.float32), axis=0).astype(g.dtype),
        new_anchors, state.global_params)
    new_state = state._replace(
        global_params=new_global, worker_params=new_wp,
        outer=outer_opt.OuterState(state.outer.v, state.outer.t + 1))
    return (new_state, new_anchors, new_v, new_res, pub_new, pub_anch_new,
            pub_v_new)


def _jit_gossip_pair(engine, donate: bool):
    fn = functools.partial(_gossip_pair_impl, engine.cfg,
                           engine.replicate_fn)
    return jax.jit(fn, donate_argnums=(0, 1, 2, 3) if donate else ())


def _jit_gossip_adopt(donate: bool):
    """Rejoin on a skipped gossip round: ``reset`` rows adopt the ``adopt``
    rows' CURRENT anchor consensus (no exchange, no outer update); the
    veterans are untouched."""
    def adopt(state, anchors, v, residual, reset, adopt):
        return _gossip_land(state, anchors, v, residual, reset, adopt, reset)

    return jax.jit(adopt, donate_argnums=(0, 1, 2, 3) if donate else ())


class _GossipRunner(SyncRunner):
    """Synchronized gossip rounds: every H steps each worker encodes its
    delta against its OWN anchor, exchanges (delta, anchors, momentum)
    with one peer from the topology schedule, and applies a per-worker
    Nesterov outer update from the pair-averaged outer state on the
    pair-averaged delta — so each pairing contracts the pair to an
    IDENTICAL outer state and the fleet gossips toward consensus.  Anchors and outer momentum live
    per worker (the runner holds them, like the EF residual —
    ``DiLoCoState`` and checkpoints are untouched); ``global_params``
    tracks the anchor mean at every sync.  With K=2 any pairing is the
    pair mean over shared anchors, so this is bit-exact ``DiLoCoSync``;
    the full topology binds ``_DiLoCoRunner`` directly (see
    ``GossipSync.bind``)."""

    supports_faults = True

    def __init__(self, engine, params, h: int, topology: str, seed: int,
                 donate: bool = True):
        from repro.core.diloco import _broadcast
        if topology == "full":
            raise ValueError("full topology is the DiLoCo mean — "
                             "GossipSync.bind delegates it to _DiLoCoRunner")
        gossip_peers(2, 0, topology, seed)   # validate the topology name
        self.engine = engine
        self.h = h
        self.topology = topology
        self.seed = seed
        self.k = engine.cfg.num_workers
        self.since = 0
        self.round = 0
        self.anchors = _broadcast(params, self.k)
        self.outer_v = jax.tree.map(
            lambda p: jnp.zeros((self.k,) + p.shape, jnp.float32), params)
        self.residual = engine.init_residual(params)
        self._donate = donate
        self._sync = _jit_gossip_pair(engine, donate)

    def bind_faults(self, tracker):
        self._tracker = tracker
        self._adoptg = _jit_gossip_adopt(self._donate)

    def _do_sync(self, state, step):
        info, records = self._round_masks(state, step)
        if info is not None and info.skip:
            if any(info.reset):
                (state, self.anchors, self.outer_v,
                 self.residual) = self._adoptg(
                    state, self.anchors, self.outer_v, self.residual,
                    jnp.asarray(info.reset), jnp.asarray(info.adopt))
            self.round += 1
            return state, records
        # deterministic matching over the contributors only: the
        # sub-fleet's schedule is mapped back through the sorted
        # contributor indices, so any two boxes replaying the same
        # schedule pair the same workers
        contributors = [w for w in range(self.k)
                        if info is None or info.contrib[w]]
        sub = gossip_peers(len(contributors), self.round, self.topology,
                           self.seed)
        peers = list(range(self.k))
        for i, w in enumerate(contributors):
            peers[w] = contributors[sub[i]]
        records += [("gossip_syncs", (step, w, peers[w], 0))
                    for w in contributors]
        records.append(("sync_steps", step))
        state, self.anchors, self.outer_v, self.residual = self._sync(
            state, self.anchors, self.outer_v, self.residual,
            jnp.asarray(peers, jnp.int32), *_masks(info))
        self.round += 1
        return state, records

    def after_step(self, state, step, loss):
        self.since += 1
        if self.since >= self.h:
            self.since = 0
            return self._do_sync(state, step)
        return state, []

    def next_event(self, step):
        return step + max(self.h - self.since, 1) - 1

    def finalize(self, state, num_steps):
        if self.since:  # trailing partial round
            return self._do_sync(state, num_steps - 1)
        return state, []

    def checkpoint_extras(self):
        if self.since:
            return None     # mid-round: defer to the gossip boundary
        return ({"anchors": self.anchors, "outer_v": self.outer_v,
                 "residual": self.residual}, {"round": self.round})

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.anchors = arrays["anchors"]
            self.outer_v = arrays["outer_v"]
            self.residual = arrays["residual"]
        self.round = int(meta["round"])


@dataclasses.dataclass(frozen=True)
class GossipSync(SyncStrategy):
    """NoLoCo-style gossip outer sync: each round every worker averages
    anchors AND deltas with ONE peer from a deterministic ``topology``
    schedule (ring / random matching / full, keyed by ``seed``), the
    delta shipped through the codec transport — so per-worker boundary
    traffic is one flat peer payload regardless of fleet size, and
    fp8/int8 wire compression of the delta composes for free."""
    name = "gossip"
    h: Optional[int] = None
    topology: str = "ring"
    seed: int = 0

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        h = self.h or engine.cfg.h_inner_steps
        if self.topology == "full" or engine.cfg.num_workers == 2:
            # the full matching — and K=2, where the one pair IS the
            # fleet — averages ALL workers at once: definitionally the
            # DiLoCo mean, so it binds the DiLoCo runner itself and the
            # equivalence is structural (bitwise by shared compilation,
            # not a per-module FMA-contraction accident)
            return _DiLoCoRunner(engine, params, FixedH(h), donate)
        return _GossipRunner(engine, params, h, self.topology, self.seed,
                             donate)

    def payload_schedule(self, n_params, num_steps, cfg):
        h = self.h or cfg.h_inner_steps
        codec = make_codec(cfg.delta_dtype)
        if self.topology == "full":
            # the DiLoCo mean: anchors are common knowledge, only the
            # codec'd deltas travel (all-gather)
            b = hop_bytes_per_worker(codec.schedule_bytes(n_params),
                                     cfg.num_workers, "gather")
        else:
            b = hop_bytes_per_worker(_gossip_payload_bytes(codec, n_params),
                                     cfg.num_workers, "peer")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="delta",
                          apply_step=s, codec=codec.name)
                for s in range(h - 1, num_steps, h)]

    def gossip_rounds(self, n_params, num_steps, cfg) -> List[GossipRound]:
        """Per-pair event model for ``comm_sim.simulate_gossip``."""
        h = self.h or cfg.h_inner_steps
        k = cfg.num_workers
        codec = make_codec(cfg.delta_dtype)
        b = (codec.schedule_bytes(n_params) if self.topology == "full"
             else _gossip_payload_bytes(codec, n_params))
        rounds = []
        for r, s in enumerate(range(h - 1, num_steps, h)):
            peers = gossip_peers(k, r, self.topology, self.seed)
            if peers is None:
                deps = tuple(tuple((j, s) for j in range(k) if j != w)
                             for w in range(k))
            else:
                deps = tuple(((peers[w], s),) if peers[w] != w else ()
                             for w in range(k))
            rounds.append(GossipRound(emit_steps=(s,) * k, deps=deps,
                                      nbytes=b, codec=codec.name))
        return rounds


class _AsyncGossipRunner(SyncRunner):
    """Gossip on per-worker step clocks: worker i syncs every
    ``periods[i] = H + jitter_i`` steps against the latest
    (delta, anchors, momentum) its peer PUBLISHED (no barrier).  The
    apply rule weights the peer contribution — outer-state mix and delta
    average alike — by its observed staleness
    s = own_step - peer_publish_step:

    * s == 0            — peer is co-due: plain 0.5/0.5 pair average;
    * 0 < s <= bound    — base weight 0.5·(1 - s/(bound+1)), further
                          scaled by the observed drift
                          ``max(cos(own_delta, peer_delta), 0)``
                          (``repro.core.drift.delta_cosine``);
    * s > bound / none  — dropped: solo outer step on the own delta.

    One fixed-signature jit applies every event (due/peer/weight/gate are
    dynamic (K,) arrays — a changing due-set never retraces); non-due
    rows pass through untouched, including their EF residual.  With
    jitter=0 and bound=0 every worker is co-due every H with staleness 0,
    and the apply specializes to the SAME jitted pair graph
    ``_GossipRunner`` uses — the reduction to the synchronous barrier is
    bit-exact by construction."""

    def __init__(self, engine, params, h: int, topology: str,
                 staleness_bound: int, jitter: int, seed: int,
                 donate: bool = True):
        if topology == "full":
            raise ValueError(
                "async gossip is peer-based; topology='full' is the "
                "synchronous DiLoCo mean — use GossipSync(topology='full') "
                "or DiLoCoSync")
        from repro.core.diloco import _broadcast
        gossip_peers(2, 0, topology, seed)   # validate the topology name
        if jitter < 0 or staleness_bound < 0:
            raise ValueError(
                f"jitter and staleness_bound must be >= 0, got "
                f"jitter={jitter} staleness_bound={staleness_bound}")
        self.engine = engine
        self.k = k = engine.cfg.num_workers
        self.h, self.topology = h, topology
        self.bound = staleness_bound
        self.seed = seed
        rng = _pyrandom.Random(seed)
        self.periods = tuple(
            h + (rng.randint(0, jitter) if jitter else 0) for _ in range(k))
        self.fully_sync = (jitter == 0 and staleness_bound == 0)
        self.anchors = _broadcast(params, k)
        self.outer_v = jax.tree.map(
            lambda p: jnp.zeros((k,) + p.shape, jnp.float32), params)
        self.residual = engine.init_residual(params)
        self.pub_step = [-(10 ** 9)] * k      # host-side publish clocks
        self.rounds = [0] * k
        if self.fully_sync:
            self.pub = self.pub_anch = self.pub_v = None
            self._apply_pair = _jit_gossip_pair(engine, donate)
        else:
            # published (decoded delta, anchors, momentum), device-held
            self.pub = jax.tree.map(
                lambda p: jnp.zeros((k,) + p.shape, jnp.float32), params)
            self.pub_anch = jax.tree.map(jnp.zeros_like, self.anchors)
            self.pub_v = jax.tree.map(jnp.zeros_like, self.outer_v)
            fn = functools.partial(_gossip_async_impl, engine.cfg,
                                   engine.replicate_fn)
            self._apply = jax.jit(
                fn, donate_argnums=(0, 1, 2, 3, 4, 5, 6) if donate else ())

    def _do_apply(self, state, step, due):
        k = self.k
        peer = list(range(k))
        base_w = [0.0] * k
        gate = [False] * k
        records = []
        for w in due:                     # publish BEFORE any read, so a
            self.pub_step[w] = step       # co-due peer is staleness 0
        for w in due:
            p = gossip_peers(k, self.rounds[w], self.topology, self.seed)[w]
            peer[w] = p
            s = step - self.pub_step[p] if self.pub_step[p] >= 0 else -1
            if p == w or s < 0 or s > self.bound:
                base_w[w] = 0.0           # drop: solo outer step
            elif s == 0:
                base_w[w] = 0.5
            else:
                base_w[w] = 0.5 * (1.0 - s / (self.bound + 1.0))
                gate[w] = True            # stale: drift-reweighted
            records.append(("gossip_syncs", (step, w, p, s)))
            self.rounds[w] += 1
        if len(due) == k:
            records.append(("sync_steps", step))
        if self.fully_sync:
            # equal clocks + bound 0: due is always the whole fleet and
            # every peer co-due — run the synchronous pair graph
            state, self.anchors, self.outer_v, self.residual = (
                self._apply_pair(state, self.anchors, self.outer_v,
                                 self.residual,
                                 jnp.asarray(peer, jnp.int32)))
            return state, records
        due_set = set(due)
        (state, self.anchors, self.outer_v, self.residual,
         self.pub, self.pub_anch, self.pub_v) = self._apply(
            state, self.anchors, self.outer_v, self.residual, self.pub,
            self.pub_anch, self.pub_v,
            jnp.asarray([w in due_set for w in range(k)], bool),
            jnp.asarray(peer, jnp.int32),
            jnp.asarray(base_w, jnp.float32),
            jnp.asarray(gate, bool))
        return state, records

    def after_step(self, state, step, loss):
        due = [w for w in range(self.k)
               if (step + 1) % self.periods[w] == 0]
        if not due:
            return state, []
        return self._do_apply(state, step, due)

    def next_event(self, step):
        return min((step // p + 1) * p - 1 for p in self.periods)

    def finalize(self, state, num_steps):
        due = [w for w in range(self.k) if num_steps % self.periods[w] != 0]
        if not due:
            return state, []
        return self._do_apply(state, num_steps - 1, due)

    def checkpoint_extras(self):
        # the publish board and clocks capture everything in flight, so
        # every chunk boundary is clean
        arrays = {"anchors": self.anchors, "outer_v": self.outer_v,
                  "residual": self.residual}
        if not self.fully_sync:
            arrays.update(pub=self.pub, pub_anch=self.pub_anch,
                          pub_v=self.pub_v)
        return arrays, {"pub_step": list(self.pub_step),
                        "rounds": list(self.rounds)}

    def load_extras(self, arrays, meta):
        if arrays is not None:
            self.anchors = arrays["anchors"]
            self.outer_v = arrays["outer_v"]
            self.residual = arrays["residual"]
            if not self.fully_sync:
                self.pub = arrays["pub"]
                self.pub_anch = arrays["pub_anch"]
                self.pub_v = arrays["pub_v"]
        self.pub_step = [int(x) for x in meta["pub_step"]]
        self.rounds = [int(x) for x in meta["rounds"]]


@dataclasses.dataclass(frozen=True)
class AsyncGossipSync(SyncStrategy):
    """Gossip on per-worker step clocks with a staleness-aware apply rule:
    worker i syncs every ``H + jitter_i`` steps (jitter drawn from
    ``seed``), consumes its peer's latest PUBLISHED delta without a
    barrier, and drops or drift-reweights contributions staler than
    ``staleness_bound`` inner steps.  ``jitter=0, staleness_bound=0`` is
    bit-exact ``GossipSync`` (the synchronous barrier)."""
    name = "async_gossip"
    h: Optional[int] = None
    topology: str = "ring"
    staleness_bound: int = 0
    jitter: int = 0
    seed: int = 0

    def bind(self, engine, params, donate: bool = True) -> SyncRunner:
        h = self.h or engine.cfg.h_inner_steps
        if (self.jitter == 0 and self.staleness_bound == 0
                and engine.cfg.num_workers == 2
                and self.topology != "full"):
            # equal clocks + bound 0 + one pair: the synchronous fleet
            # mean — same structural delegation as GossipSync at K=2
            # (full still falls through to the runner's rejection)
            gossip_peers(2, 0, self.topology, self.seed)  # validate name
            return _DiLoCoRunner(engine, params, FixedH(h), donate)
        return _AsyncGossipRunner(engine, params, h, self.topology,
                                  self.staleness_bound, self.jitter,
                                  self.seed, donate)

    def _periods(self, h: int, k: int) -> Tuple[int, ...]:
        rng = _pyrandom.Random(self.seed)
        return tuple(
            h + (rng.randint(0, self.jitter) if self.jitter else 0)
            for _ in range(k))

    def payload_schedule(self, n_params, num_steps, cfg):
        # the mean worker's footprint: one peer payload every ~H steps,
        # with the staleness window as overlap budget; the per-worker
        # event model (per-pair barriers, per-worker clocks) is
        # gossip_rounds + comm_sim.simulate_gossip
        h = self.h or cfg.h_inner_steps
        codec = make_codec(cfg.delta_dtype)
        b = hop_bytes_per_worker(_gossip_payload_bytes(codec, n_params),
                                 cfg.num_workers, "peer")
        return [SyncEvent(step=s, bytes_per_worker=b, kind="delta",
                          apply_step=s + self.staleness_bound,
                          codec=codec.name)
                for s in range(h - 1, num_steps, h)]

    def gossip_rounds(self, n_params, num_steps, cfg) -> List[GossipRound]:
        """Replay the runner's publish/consume schedule as simulator
        events: one ``GossipRound`` per step with due workers, pair deps
        only for consumed (non-dropped) contributions."""
        h = self.h or cfg.h_inner_steps
        k = cfg.num_workers
        codec = make_codec(cfg.delta_dtype)
        b = _gossip_payload_bytes(codec, n_params)
        periods = self._periods(h, k)
        pub = [-(10 ** 9)] * k
        rounds_count = [0] * k
        out = []
        for step in range(num_steps):
            due = [w for w in range(k) if (step + 1) % periods[w] == 0]
            if not due:
                continue
            for w in due:
                pub[w] = step
            emit = [-1] * k
            deps: List[Tuple] = [()] * k
            for w in due:
                emit[w] = step
                p = gossip_peers(k, rounds_count[w], self.topology,
                                 self.seed)[w]
                s = step - pub[p]
                if p != w and s <= self.staleness_bound:
                    deps[w] = ((p, pub[p]),)
                rounds_count[w] += 1
            out.append(GossipRound(emit_steps=tuple(emit), deps=tuple(deps),
                                   nbytes=b, codec=codec.name))
        return out


# ---------------------------------------------------------------------------
# Config-driven construction — declarative method -> factory registry
# ---------------------------------------------------------------------------

# name -> factory(cfg, h_schedule) -> SyncStrategy.  New strategies register
# in one line; launch.train derives its --method choices from this table.
_STRATEGY_REGISTRY: Dict[str, Any] = {}


def register_strategy(name: str):
    """Decorator: register a ``factory(cfg, h_schedule) -> SyncStrategy``
    under ``name`` (the ``DiLoCoConfig.strategy`` spelling)."""
    def deco(factory):
        _STRATEGY_REGISTRY[name] = factory
        return factory
    return deco


def strategy_names() -> Tuple[str, ...]:
    """Registered strategy names, in registration order."""
    return tuple(_STRATEGY_REGISTRY)


@register_strategy("ddp")
def _ddp_factory(cfg, h_schedule):
    return DDPSync()


@register_strategy("ddp_compressed")
def _ddp_compressed_factory(cfg, h_schedule):
    return CompressedDDPSync()


@register_strategy("diloco")
def _diloco_factory(cfg, h_schedule):
    return DiLoCoSync(h_schedule=h_schedule)


@register_strategy("streaming")
def _streaming_factory(cfg, h_schedule):
    return StreamingSync(num_fragments=cfg.num_fragments)


@register_strategy("overlapped")
def _overlapped_factory(cfg, h_schedule):
    return OverlappedSync(delay=cfg.sync_delay, jitter=cfg.h_jitter,
                          seed=cfg.sync_seed)


@register_strategy("pipelined")
def _pipelined_factory(cfg, h_schedule):
    return PipelinedSync(num_fragments=cfg.num_fragments,
                         delay=cfg.sync_delay)


@register_strategy("gossip")
def _gossip_factory(cfg, h_schedule):
    return GossipSync(topology=cfg.topology, seed=cfg.sync_seed)


@register_strategy("async_gossip")
def _async_gossip_factory(cfg, h_schedule):
    return AsyncGossipSync(topology=cfg.topology,
                           staleness_bound=cfg.staleness_bound,
                           jitter=cfg.h_jitter, seed=cfg.sync_seed)


STRATEGIES = strategy_names()


def make_strategy(cfg: DiLoCoConfig, h_schedule: Optional[HSchedule] = None
                  ) -> SyncStrategy:
    """Build the strategy the ``DiLoCoConfig`` knobs describe (registry
    lookup — see ``register_strategy``)."""
    factory = _STRATEGY_REGISTRY.get(cfg.strategy)
    if factory is None:
        raise ValueError(f"unknown strategy {cfg.strategy!r}; "
                         f"expected one of {strategy_names()}")
    return factory(cfg, h_schedule)
