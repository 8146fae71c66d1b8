"""Fault tolerance: schedule DSL, quorum math, elastic rejoin,
crash-consistent resume, comm-sim fault overlay, prefetcher error
surfacing, and graceful serving degradation.

The anchors, in order of strictness:

* masked quorum averaging with an all-live mask is BITWISE the unmasked
  expression (the no-fault path never pays for fault support);
* a K=4 outer round with one dead worker is BITWISE a K=3 round on the
  survivors, pinned at the outer-step level with identical per-row
  deltas (the vmapped inner chunk compiles different reduction blockings
  for different K, so the full-trainer comparison can only be allclose);
* kill -> checkpoint -> --resume continues BITWISE vs an uninterrupted
  run (state, loss history, sync steps), for DDP and DiLoCo.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_cfg
from repro.checkpoint import (latest_run_checkpoint, list_run_checkpoints,
                              load_run_checkpoint, save_run_checkpoint)
from repro.checkpoint.checkpoint import _atomic_bytes
from repro.configs.base import DiLoCoConfig, OptimizerConfig
from repro.core import DistTrainer, make_strategy
from repro.core import outer_opt
from repro.core.diloco import DiLoCoTrainer
from repro.core.faults import (FaultEvent, FaultSchedule, FleetTracker,
                               SimulatedCrash)
from repro.data.pipeline import Prefetcher
from repro.launch.comm_sim import CommModel, simulate_gossip, \
    simulate_heterogeneous
from repro.models.transformer import build_model, init_params
from repro.serving import KVBlockPool, PrefixTree, Request, Scheduler

OPT = OptimizerConfig(total_steps=100, warmup_steps=0, schedule="constant",
                      learning_rate=0.02, adam_lr=1e-3)


def _setup(k=2, h=4, **dkw):
    cfg = tiny_cfg("dense")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    dcfg = DiLoCoConfig(num_workers=k, h_inner_steps=h, **dkw)
    return cfg, m, params, dcfg


def _data(cfg, k, step, B=2, S=16):
    key = jax.random.key(1000 + step)
    toks = jax.random.randint(key, (k, B, S), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": (toks + 1) % cfg.vocab_size}


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Schedule DSL + tracker bookkeeping
# ---------------------------------------------------------------------------

def test_from_spec_parses_every_kind():
    fs = FaultSchedule.from_spec(
        "crash:2@10, rejoin:2@20, slow:1@5x1.5, drop:3@9x2, "
        "corrupt:0@4, kill@30")
    by_kind = {e.kind: e for e in fs.events}
    assert (by_kind["crash"].worker, by_kind["crash"].step) == (2, 10)
    assert (by_kind["rejoin"].worker, by_kind["rejoin"].step) == (2, 20)
    assert by_kind["slow"].factor == 1.5
    assert by_kind["drop"].attempts == 2
    assert by_kind["corrupt"].attempts == 1
    assert (by_kind["kill"].step, by_kind["kill"].worker) == (30, -1)
    assert FaultSchedule.from_spec("").empty and not fs.empty
    # kill is process-level: it never binds the per-worker fault jits
    assert all(e.kind != "kill" for e in fs.worker_events())
    assert len(fs.worker_events()) == 5


def test_schedule_roundtrip_validate_and_seeded_random(tmp_path):
    fs = FaultSchedule.from_spec("crash:2@10,rejoin:2@20,kill@30")
    p = str(tmp_path / "faults.json")
    fs.save(p)
    assert FaultSchedule.load(p).events == fs.events
    assert FaultSchedule.from_spec(p).events == fs.events   # path spelling
    fs.validate(4)
    with pytest.raises(ValueError, match="outside the fleet"):
        fs.validate(2)
    # the seeded draw IS the script: same args, same schedule, anywhere
    a = FaultSchedule.random(8, 40, seed=3, crashes=2, rejoin_after=10)
    b = FaultSchedule.random(8, 40, seed=3, crashes=2, rejoin_after=10)
    assert a.events == b.events
    assert sum(e.kind == "crash" for e in a.events) == 2
    assert sum(e.kind == "rejoin" for e in a.events) == 1


def test_chunk_limit_splits_at_crash_and_kill():
    fs = FaultSchedule.from_spec("crash:1@5,kill@9")
    assert fs.chunk_limit(0) == 4     # chunk must END before the mask flips
    assert fs.chunk_limit(5) == 9     # ...and AT a kill (process dies after)
    assert fs.chunk_limit(10) is None
    tr = FleetTracker(FaultSchedule.from_spec("crash:0@3,rejoin:0@6"), 2)
    live, _ = tr.begin_chunk(0)
    assert live == (True, True)
    live, recs = tr.begin_chunk(3)
    assert live == (False, True)
    assert ("fault", (3, "crash", 0)) in recs


# ---------------------------------------------------------------------------
# Quorum math
# ---------------------------------------------------------------------------

def test_masked_average_all_live_is_bitwise_the_unmasked_mean():
    """ISSUE anchor: masked mean over an all-ones mask == the unmasked
    mean, bitwise, for both plain and drift-aware averaging."""
    delta = {"a": jax.random.normal(jax.random.key(0), (4, 8, 3)),
             "b": jax.random.normal(jax.random.key(1), (4, 5))}
    ones = jnp.ones(4, bool)
    full_fn = jax.jit(
        lambda d, drift: outer_opt._average(
            d, DiLoCoConfig(num_workers=4, drift_aware=drift)),
        static_argnums=1)
    masked_fn = jax.jit(
        lambda d, l, drift: outer_opt._average(
            d, DiLoCoConfig(num_workers=4, drift_aware=drift), live=l),
        static_argnums=2)
    for drift in (False, True):
        _assert_tree_equal(full_fn(delta, drift),
                           masked_fn(delta, ones, drift))


def _noise_row(params, seed, scale=0.01):
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(
        treedef, [l + scale * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])


def test_quorum_one_dead_matches_survivor_fleet_bitwise():
    """Structural anchor: a K=4 quorum round with worker 3 dead is the
    K=3 quorum round on the survivors — pinned BITWISE at the outer-step
    level with identical per-row worker params (the masked sum adds a
    literal zero row, which is exact; the dead row passes through
    frozen).  Against PLAIN K=3 DiLoCo the comparison is allclose-tight
    rather than bitwise for a reason unrelated to the quorum math:
    ``jnp.mean`` lowers to ``sum * (1/n)``, and 1/3 is not representable
    — sum/3 lands 1 ulp away (1/4 is exact, which is why the all-live
    K=4 test above IS bitwise)."""
    cfg = tiny_cfg("dense")
    params, _ = init_params(cfg, jax.random.key(0))
    rows = [_noise_row(params, 100 + i) for i in range(4)]

    def with_rows(eng, rs):
        st = eng.init(params)
        return st._replace(worker_params=jax.tree.map(
            lambda *r: jnp.stack(r), *rs))

    eng4 = DiLoCoTrainer(None, OPT, DiLoCoConfig(num_workers=4))
    eng3 = DiLoCoTrainer(None, OPT, DiLoCoConfig(num_workers=3))
    st4, st3 = with_rows(eng4, rows), with_rows(eng3, rows[:3])
    contrib = jnp.array([1, 1, 1, 0], bool)
    new4, _ = jax.jit(eng4.outer_step_ef)(
        st4, None, contrib, contrib, jnp.zeros(4, bool))
    new3q, _ = jax.jit(eng3.outer_step_ef)(
        st3, None, jnp.ones(3, bool), jnp.ones(3, bool), jnp.zeros(3, bool))
    _assert_tree_equal(new4.global_params, new3q.global_params)
    # live rows adopt the (identical) new anchor...
    for w in range(3):
        _assert_tree_equal(
            jax.tree.map(lambda x: x[w], new4.worker_params),
            jax.tree.map(lambda x: x[w], new3q.worker_params))
    # ...and the dead row passes through frozen, bit-for-bit
    _assert_tree_equal(
        jax.tree.map(lambda x: x[3], new4.worker_params), rows[3])
    # plain K=3 DiLoCo: identical up to the mean's reciprocal rounding
    new3 = jax.jit(eng3.outer_step)(st3)
    for x, y in zip(jax.tree.leaves(new4.global_params),
                    jax.tree.leaves(new3.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-6, rtol=0)


def _selects(jitted, *args) -> int:
    return jitted.lower(*args).compile().as_text().count(" select(")


def _fleet_program(program, k):
    """``(fn, args, n_masks, reference_selects)`` for one program at
    K=``k`` on the tiny dense config: the unjitted program, its arguments
    before the fleet masks, how many (K,) masks it takes, and the
    ``select`` count of the same program written with no fleet masks."""
    from repro.core.streaming import fragment_masks
    cfg, m, params, dcfg = _setup(k=k, h=2, strategy="pipelined",
                                  num_fragments=2)
    dt = DistTrainer(m.loss, OPT, dcfg, make_strategy(dcfg))
    eng, st = dt.engine(), dt.init(params)
    # divergent workers with live optimizer moments, so that every mask
    # (which rows average, adopt, reset) shows in the result
    st = st._replace(
        worker_params=jax.tree.map(
            lambda *r: jnp.stack(r),
            *[_noise_row(params, 200 + i) for i in range(k)]),
        inner_opt=jax.tree.map(lambda x: x + 0.5, st.inner_opt))
    frag = fragment_masks(params, 2)[1]
    if program == "outer_step":
        return eng.outer_step_ef, (st, None), 3, 0
    if program == "fragment_step":
        # the global and worker merges of every leaf: the fragment mask
        # is an argument here
        return (eng.outer_step_fragment_ef, (st, frag, None), 3,
                2 * len(jax.tree.leaves(frag)))
    if program == "pipelined_apply":
        runner = dt.strategy.bind(eng, params, donate=False)
        snap = jax.tree.map(lambda w: w * 0.5, st.worker_params)
        # the runner's fragment masks are constants, so only the leaves
        # that the fragment splits keep their two merges
        mixed = sum(1 for x in jax.tree.leaves(frag) if 0 < x.sum() < x.size)
        return (functools.partial(runner._apply_impl, frag=1),
                (st, snap, None), 3, 2 * mixed)
    batches = {key: jnp.stack([v, v[::-1]])
               for key, v in _data(cfg, k, 0).items()}

    def plain_chunk(state, bs):
        def body(s, b):
            s, loss, _ = eng.inner_step(s, b)
            return s, loss
        return jax.lax.scan(body, state, bs)

    return (eng.inner_chunk, (st, batches), 1,
            _selects(jax.jit(plain_chunk), st, batches))


def _all_live(k, n_masks):
    """(live/contrib, adopt, reset)[:n_masks] for a fleet with no fault."""
    return (jnp.ones(k, bool), jnp.ones(k, bool), jnp.zeros(k, bool))[:n_masks]


@pytest.mark.parametrize("program", ["outer_step", "fragment_step",
                                     "pipelined_apply", "inner_chunk"])
def test_whole_fleet_default_masks_fold_to_the_unmasked_program(program):
    """One program per strategy serves faulty and fault-free runs.

    * Left out, the fleet masks mean the whole fleet: the default call is
      bitwise the call with all-live masks built inside the trace.
    * XLA folds the default masks away: the compiled program has as many
      ``select`` ops as one written with no fleet masks at all (none for
      the DiLoCo outer step, at K=1 and K=4).
    * All-live masks passed as traced arrays compute the same state.  That
      program keeps its selects, and XLA:CPU contracts mul+add chains into
      FMAs per compiled module, so it agrees to float32 rounding, not
      bitwise: a few ulps of values below 1 after at most two optimizer
      steps.
    """
    fn, args, n_masks, reference = _fleet_program(program, 4)
    default = jax.jit(fn)
    out = default(*args)
    _assert_tree_equal(
        out, jax.jit(lambda *a: fn(*a, *_all_live(4, n_masks)))(*args))
    assert _selects(default, *args) == reference
    traced = default(*args, *_all_live(4, n_masks))
    for x, y in zip(jax.tree.leaves(out), jax.tree.leaves(traced)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=0, atol=1e-6)
    if program == "outer_step":
        fn, args, _, reference = _fleet_program(program, 1)
        assert _selects(jax.jit(fn), *args) == reference


# ---------------------------------------------------------------------------
# End-to-end fault runs
# ---------------------------------------------------------------------------

def _run_with(dcfg, cfg, m, params, steps, k, **kw):
    strat = make_strategy(dcfg)
    dt = DistTrainer(m.loss, OPT, dcfg, strat)
    state = dt.init(params)
    return dt.run(state, lambda s: _data(cfg, k, s), steps, **kw)


def test_crash_rejoin_end_to_end_records_and_trains():
    cfg, m, params, dcfg = _setup(k=4, h=3, strategy="diloco")
    faults = FaultSchedule.from_spec(
        "slow:3@2x1.5,crash:2@4,drop:1@5,rejoin:2@10")
    state, hist = _run_with(dcfg, cfg, m, params, 12, 4, faults=faults)
    assert hist["sync_steps"] == [2, 5, 8, 11]
    # quorum shrinks at the crash and stays shrunk until the rejoin round
    assert hist["quorum"] == [(2, 4), (5, 3), (8, 3), (11, 3)]
    fault_recs = hist["fault"]
    assert (4, "crash", 2) in fault_recs
    assert (5, "drop_retry", 1) in fault_recs
    assert (2, "slow", 3, 1.5) in fault_recs
    assert (11, "rejoin", 2) in fault_recs
    # rejoin drift metrics logged exactly once, at the rejoin boundary
    (step, worker, norm, cos), = hist["rejoin_drift"]
    assert (step, worker) == (11, 2)
    assert np.isfinite(norm) and np.isfinite(cos)
    assert np.isfinite(hist["loss"]).all()


def test_min_quorum_skips_round_and_anchor_stays_put():
    cfg, m, params, dcfg = _setup(k=2, h=3, strategy="diloco")
    faults = FaultSchedule.from_spec("crash:1@2")
    state, hist = _run_with(dcfg, cfg, m, params, 9, 2, faults=faults,
                            min_quorum=2)
    assert hist["quorum_skip"] == [2, 5, 8]
    assert hist["sync_steps"] == []
    # every round below quorum: the anchor never moves off init
    _assert_tree_equal(state.global_params, params)


def test_drop_retry_keeps_worker_in_and_matches_no_fault_run():
    cfg, m, params, dcfg = _setup(k=2, h=4, strategy="diloco")
    base_state, base_hist = _run_with(dcfg, cfg, m, params, 8, 2)
    # one failed attempt -> codec-aware retry succeeds, worker stays in:
    # full quorum, same math as the no-fault round (allclose-tight — the
    # outer step with traced masks is a different compiled program from
    # its whole-fleet default, so XLA's fusion choices may differ by an
    # ulp; BITWISE no-fault equality is pinned on the empty-schedule path,
    # which passes no masks)
    faults = FaultSchedule.from_spec("drop:1@3")
    state, hist = _run_with(dcfg, cfg, m, params, 8, 2, faults=faults)
    assert (3, "drop_retry", 1) in hist["fault"]
    assert hist["quorum"] == [(3, 2), (7, 2)]
    for x, y in zip(jax.tree.leaves(state.global_params),
                    jax.tree.leaves(base_state.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6,
                                   rtol=0)
    np.testing.assert_allclose(hist["loss"], base_hist["loss"], rtol=1e-4)
    # two failed attempts -> counted out of this round's average
    faults = FaultSchedule.from_spec("corrupt:1@3x2")
    _, hist = _run_with(dcfg, cfg, m, params, 8, 2, faults=faults)
    assert (3, "corrupt_lost", 1) in hist["fault"]
    assert hist["quorum"] == [(3, 1), (7, 2)]


# ---------------------------------------------------------------------------
# Crash-consistent auto-resume (the honesty anchor)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["ddp", "diloco"])
def test_kill_checkpoint_resume_is_bitwise(tmp_path, strategy):
    """kill@7 with outer-boundary checkpoints every 6 steps, then
    --resume: the continuation is BITWISE an uninterrupted 12-step run —
    final state, recorded losses, and sync steps.  Kill-only schedules
    never bind the fault jits, so the compiled programs are the
    uninterrupted run's."""
    if strategy == "ddp":
        cfg, m, params, dcfg = _setup(
            k=1, h=1, strategy="ddp", outer_lr=1.0, outer_momentum=0.0,
            nesterov=False)
        k = 1
    else:
        cfg, m, params, dcfg = _setup(k=2, h=3, strategy="diloco")
        k = 2
    base_state, base_hist = _run_with(dcfg, cfg, m, params, 12, k)

    ckpt = str(tmp_path / strategy)
    with pytest.raises(SimulatedCrash, match="after step 7"):
        _run_with(dcfg, cfg, m, params, 12, k,
                  faults=FaultSchedule.from_spec("kill@7"),
                  checkpoint_dir=ckpt, checkpoint_every=6)
    assert [s for s, _ in list_run_checkpoints(ckpt)] == [6]
    state, hist = _run_with(dcfg, cfg, m, params, 12, k,
                            checkpoint_dir=ckpt, checkpoint_every=6,
                            resume=True)
    _assert_tree_equal(state, base_state)
    assert hist["step"] == base_hist["step"]
    np.testing.assert_array_equal(hist["loss"], base_hist["loss"])
    assert hist["sync_steps"] == base_hist["sync_steps"]


def test_torn_checkpoint_falls_back_to_previous(tmp_path):
    d = str(tmp_path)
    s1 = {"w": np.arange(4, dtype=np.float32)}
    s2 = {"w": np.arange(4, dtype=np.float32) * 2}
    save_run_checkpoint(d, 2, s1, history={"loss": [1.0]})
    save_run_checkpoint(d, 4, s2)
    assert [s for s, _ in list_run_checkpoints(d)] == [2, 4]
    # torn write: the newest state file vanished mid-crash -> its
    # manifest is incomplete and the reader degrades to the previous step
    os.remove(os.path.join(d, "ckpt_00000004.state.npz"))
    assert [s for s, _ in list_run_checkpoints(d)] == [2]
    man = latest_run_checkpoint(d)
    assert man["step"] == 2 and man["history"] == {"loss": [1.0]}
    state, _ = load_run_checkpoint(man, {"w": np.zeros(4, np.float32)})
    np.testing.assert_array_equal(state["w"], s1["w"])
    # garbage manifest (torn json): skipped, not fatal
    with open(os.path.join(d, "ckpt_00000006.json"), "w") as f:
        f.write('{"step": 6, "files": {')
    assert [s for s, _ in list_run_checkpoints(d)] == [2]


def test_atomic_write_crash_leaves_old_file_and_no_tmp(tmp_path):
    p = str(tmp_path / "manifest.json")
    _atomic_bytes(p, lambda f: f.write(b"old"))

    def boom(f):
        f.write(b"torn")
        raise RuntimeError("crash mid-write")

    with pytest.raises(RuntimeError, match="mid-write"):
        _atomic_bytes(p, boom)
    with open(p, "rb") as f:
        assert f.read() == b"old"
    assert os.listdir(str(tmp_path)) == ["manifest.json"]


# ---------------------------------------------------------------------------
# Comm-sim fault overlay
# ---------------------------------------------------------------------------

def test_comm_sim_empty_schedule_reduces_to_fault_free():
    """Property: the fault overlay with an empty schedule is the
    identity — dict-exact against the pre-existing simulator output."""
    dcfg = DiLoCoConfig(num_workers=4, h_inner_steps=5, strategy="diloco")
    evs = make_strategy(dcfg).payload_schedule(10_000, 20, dcfg)
    comm = CommModel(bandwidth=1e8, latency=1e-3)
    times = [0.010, 0.012, 0.009, 0.011]
    base = simulate_heterogeneous(evs, 20, times, comm)
    assert simulate_heterogeneous(evs, 20, times, comm,
                                  faults=FaultSchedule()) == base
    assert base["retry_bytes"] == 0.0
    # a crash changes the timeline; a drop pays retry bytes
    crashed = simulate_heterogeneous(
        evs, 20, times, comm, faults=FaultSchedule.from_spec("crash:2@4"))
    assert crashed != base
    dropped = simulate_heterogeneous(
        evs, 20, times, comm, faults=FaultSchedule.from_spec("drop:0@4"))
    assert dropped["retry_bytes"] > 0
    assert dropped["wall_clock_s"] >= base["wall_clock_s"]


def test_comm_sim_gossip_empty_schedule_reduces_to_fault_free():
    dcfg = DiLoCoConfig(num_workers=4, h_inner_steps=5, strategy="gossip")
    rounds = make_strategy(dcfg).gossip_rounds(10_000, 20, dcfg)
    comm = CommModel(bandwidth=1e8, latency=1e-3)
    times = [0.010, 0.012, 0.009, 0.011]
    base = simulate_gossip(rounds, 20, times, comm)
    assert simulate_gossip(rounds, 20, times, comm,
                           faults=FaultSchedule()) == base
    assert base["retry_bytes"] == 0.0
    slowed = simulate_gossip(
        rounds, 20, times, comm,
        faults=FaultSchedule.from_spec("slow:1@2x2.0"))
    assert slowed["wall_clock_s"] > base["wall_clock_s"]


# ---------------------------------------------------------------------------
# Prefetcher error surfacing
# ---------------------------------------------------------------------------

def test_prefetcher_surfaces_original_producer_exception():
    class Boom(RuntimeError):
        pass

    def flaky(step):
        if step == 3:
            raise Boom("bad shard 3")
        return {"x": np.full((2,), step, np.float32)}

    pf = Prefetcher(flaky, 10, depth=2)
    try:
        out = pf.take(0, 3)
        np.testing.assert_array_equal(np.asarray(out["x"])[:, 0], [0, 1, 2])
        with pytest.raises(Boom, match="bad shard 3") as einfo:
            pf.take(3, 2)
        # the ORIGINAL exception object, traceback pointing into data_fn
        assert einfo.value.__cause__ is None
        import traceback
        frames = traceback.extract_tb(einfo.value.__traceback__)
        assert any(f.name == "flaky" for f in frames)
    finally:
        pf.close()


def test_prefetcher_dead_producer_clean_shutdown_error():
    pf = Prefetcher(lambda s: {"x": np.zeros(2, np.float32)}, 8, depth=2)
    pf.take(0, 2)
    # simulate a producer shut down cleanly (no recorded error) while the
    # consumer still wants data: take() must fail loudly, not hang or
    # return garbage
    pf._stop.set()
    pf._thread.join(timeout=5)
    import queue as _q
    while True:
        try:
            pf._q.get_nowait()
        except _q.Empty:
            break
    pf._q.put((None, Prefetcher._DONE))
    with pytest.raises(RuntimeError, match="stopped \\(closed\\)"):
        pf.take(2, 1)


# ---------------------------------------------------------------------------
# Serving graceful degradation
# ---------------------------------------------------------------------------

def test_scheduler_deadline_and_cancel_return_every_resource():
    """Deadline expiry and cancellation are ledger-clean: every KV block,
    budget reservation, and prefix-tree reference comes back exactly as a
    natural completion — pool invariants hold after every phase and the
    pool drains to pristine."""
    bs = 8
    pool = KVBlockPool(32, bs)
    tree = PrefixTree(block_size=bs)
    sched = Scheduler(4, pool, max_blocks_per_slot=8, tree=tree)
    shared = [7] * 20
    reqs = [Request(rid=i, prompt=shared + [i] * 5, max_new=4,
                    deadline_s=(0.5 if i % 2 else None))
            for i in range(8)]
    for r in reqs:
        sched.submit(r)
    admitted = sched.admit(0.0)
    assert len(admitted) == 4
    pool.check_invariants()
    # prefill slot 0 fully and publish its prompt to the prefix cache
    si0 = admitted[0]
    slot0 = sched.slots[si0]
    sched.ensure_mapped(si0, len(slot0.req.prompt) - 1)
    tree.insert(slot0.req.prompt, [b for b in slot0.blocks if b >= 0], pool)
    assert tree.num_blocks == 4     # 3 full chunks + partial tail leaf
    pool.check_invariants()
    # t=1.0: every odd-rid request is past its 0.5s deadline
    evicted = sched.expire(1.0)
    assert sorted(r.rid for _, r in evicted) == [1, 3, 5, 7]
    assert all(r.expired and r.finish_time == 1.0 for _, r in evicted)
    waiting_evictions = [r for si, r in evicted if si is None]
    running_evictions = [r for si, r in evicted if si is not None]
    assert len(waiting_evictions) == 2 and len(running_evictions) == 2
    pool.check_invariants()
    # freed slots re-admit the survivors, who attach the cached prefix
    newly = sched.admit(1.0)
    assert sorted(sched.slots[si].req.rid for si in newly) == [4, 6]
    assert all(sched.slots[si].num_shared == 2 for si in newly)
    assert sched.prefix_hits == 2
    pool.check_invariants()
    # cancel everything still live; unknown rid is a no-op
    for r in reqs:
        sched.cancel(r.rid, now=2.0)
    assert sched.cancel(999) is None
    assert all(s is None for s in sched.slots) and not sched.waiting
    pool.check_invariants()
    # only the tree's references remain; evicting them drains to pristine
    assert pool.num_allocated == tree.num_blocks == 4
    assert pool.num_reserved == 0
    tree.evict(pool, tree.num_blocks)
    pool.check_invariants()
    assert pool.num_allocated == 0 and pool.num_free == 32


def test_engine_expires_past_deadline_requests():
    cfg = tiny_cfg("dense")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    from repro.serving import Engine
    eng = Engine(m, params, num_slots=2, max_len=64, block_size=8)
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new=3),
            Request(rid=1, prompt=[4, 5], max_new=3, deadline_s=-1.0)]
    stats = eng.run(reqs, use_time=True)
    assert stats["expired"] == 1
    assert reqs[1].expired and not reqs[1].tokens
    assert not reqs[0].expired and len(reqs[0].tokens) == 3
    # without use_time, deadlines are inert (now is never sampled)
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new=3, deadline_s=-1.0)]
    stats = eng.run(reqs)
    assert stats["expired"] == 0 and len(reqs[0].tokens) == 3
