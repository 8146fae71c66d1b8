"""Training launcher — the paper's pipeline as a CLI.

Runs the three-stage nanochat pipeline (base pretrain -> dialogue mid-train
-> SFT) under any of the three configurations the paper compares:

  --method ddp         fully synchronous baseline
  --method diloco      DiLoCo wrapper (H, mu, eta from the paper)
  --method streaming   Streaming DiLoCo (fragment-wise staggered sync)
  --method overlapped  delayed outer application + straggler jitter
  --method pipelined   DiLoCoX shape: one fragment per round, delayed apply
  --method gossip      no-all-reduce peer averaging (--topology ring|random|full)
  --method async_gossip gossip on per-worker clocks (H + jitter_i) with a
                       staleness-aware apply rule (--staleness-bound)
  --method hybrid      DiLoCo base, DDP mid+SFT (checkpoint hand-off)

``--method`` accepts any name registered in ``repro.core.sync`` (the list
above plus whatever plugins register_strategy() added) and "hybrid".

``--sync-dtype f32|bf16|int8|fp8|e5m2`` picks the outer-sync wire codec
(int8/fp8 add per-tensor scales + error feedback, see repro.core.transport);
``--grad-compress int8|fp8`` turns ``--method ddp`` into K real workers
exchanging per-step updates through the same codec stack (CompressedDDPSync);
``--worker-speeds 1,1,1.2,1.5`` models a heterogeneous fleet: after the
run, the comm simulator replays the sync schedule with per-worker step
clocks (calibrated from the measured inner-step seconds of the base
stage) and reports the modeled homogeneous vs heterogeneous wall-clock.

The corpora are synthetic (see repro.data.synthetic).  ``--arch tiny``
(the default) is a 4-layer toy for CPU runs; ``--arch <name>`` builds any
registered architecture at its published widths and vocab, and
``--reduced`` asks for its 2-layer CPU-sized variant instead.

Examples:
  PYTHONPATH=src python -m repro.launch.train --method diloco --steps 200
  PYTHONPATH=src python -m repro.launch.train --method hybrid --arch nanochat-d20 --reduced
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, Optional, Sequence

import jax


def build_pipeline(vocab_budget: int = 512, seq_len: int = 128,
                   n_pretrain: int = 6000, seed: int = 0):
    """Tokenizer + three-stage datasets + eval suites (synthetic world)."""
    from repro.data import PackedDataset, build_tokenizer, synthetic
    world = synthetic.World.make(40, seed=1234 + seed)
    pre_texts = synthetic.gen_pretrain_texts(world, n_pretrain, seed=seed)
    tok = build_tokenizer(pre_texts[:2000], vocab_budget)
    stages = {
        "base": PackedDataset.from_texts(pre_texts, tok, seq_len),
        "mid": PackedDataset.from_texts(
            synthetic.gen_dialogue_texts(world, n_pretrain // 2, seed=seed + 1),
            tok, seq_len),
        "sft": PackedDataset.from_texts(
            synthetic.gen_sft_texts(world, n_pretrain // 2, seed=seed + 2),
            tok, seq_len),
    }
    suites = {
        "mc": synthetic.gen_mc_eval(world, 32, seed=7),
        "arith": synthetic.gen_arith_eval(32, seed=8),
        "pattern": synthetic.gen_pattern_eval(32, seed=9),
    }
    return world, tok, stages, suites


def make_model(arch: str, reduced: bool, vocab_size: int):
    from repro.configs import get_config, get_reduced
    from repro.models import build_model
    if arch == "tiny":
        from repro.configs.base import ModelConfig
        cfg = ModelConfig(name="tiny-nanochat", num_layers=4, d_model=128,
                          num_heads=4, num_kv_heads=4, d_ff=512,
                          vocab_size=vocab_size, tie_embeddings=True)
    elif reduced:
        cfg = get_reduced(arch).with_(vocab_size=vocab_size)
    else:
        # a published config keeps its own vocab: the tokenizer's ids are
        # a subset of it (it grows only for a tokenizer that needs more)
        cfg = get_config(arch)
        cfg = cfg.with_(vocab_size=max(cfg.vocab_size, vocab_size))
    return cfg, build_model(cfg)


def run_stage(method: str, model, params, stage_ds, *, steps: int,
              workers: int, per_worker_batch: int, h: int,
              opt_cfg, diloco_cfg, seed: int = 0,
              h_schedule=None, prefetch: int = 0,
              faults=None, min_quorum: int = 1,
              checkpoint_dir=None, checkpoint_every: int = 0,
              resume: bool = False):
    """Run one pipeline stage under any sync strategy; returns
    (final params, history).  All methods go through the unified
    ``DistTrainer`` runtime — ``method`` picks the ``SyncStrategy``.
    ``params`` are consumed (donated to the run): use the returned ones."""
    import dataclasses
    import jax.numpy as jnp
    from repro.core import DistTrainer, make_strategy

    if method == "ddp" and diloco_cfg.grad_compress not in ("", "none"):
        # DDP-side gradient compression: K real workers exchanging their
        # per-step updates through the codec (core.sync.CompressedDDPSync)
        from repro.core.sync import compressed_ddp_config
        dcfg = compressed_ddp_config(
            dataclasses.replace(diloco_cfg, num_workers=workers))

        def data(step):
            b = stage_ds.worker_batches(step, workers, per_worker_batch,
                                        seed=seed)
            return {k: jnp.asarray(v) for k, v in b.items()}
    elif method == "ddp":
        dcfg = dataclasses.replace(diloco_cfg, num_workers=1,
                                   h_inner_steps=1, outer_lr=1.0,
                                   outer_momentum=0.0, nesterov=False,
                                   strategy="ddp")

        def data(step):
            b = stage_ds.batch(step, workers * per_worker_batch, seed=seed)
            return {k: jnp.asarray(v)[None] for k, v in b.items()}
    else:
        # clamp the overlap knobs to the stage's H (stage budgets can shrink
        # H below a globally-configured delay/jitter)
        delay = min(diloco_cfg.sync_delay, h - 1)
        jitter = min(diloco_cfg.h_jitter, h - 1 - delay)
        dcfg = dataclasses.replace(diloco_cfg, num_workers=workers,
                                   h_inner_steps=h, strategy=method,
                                   sync_delay=delay, h_jitter=jitter)

        def data(step):
            b = stage_ds.worker_batches(step, workers, per_worker_batch,
                                        seed=seed)
            return {k: jnp.asarray(v) for k, v in b.items()}

    trainer = DistTrainer(model.loss, opt_cfg, dcfg,
                          make_strategy(dcfg, h_schedule=h_schedule))
    # the stage owns its trainer state: hand it to the run without the
    # defensive copy (at published widths two copies of params + outer +
    # optimizer state do not fit one chip).  The state's anchor IS
    # ``params``, so the caller's ``params`` are consumed too.
    state, hist = trainer.run(trainer.init(params), data, steps,
                              prefetch=prefetch, faults=faults,
                              min_quorum=min_quorum,
                              checkpoint_dir=checkpoint_dir,
                              checkpoint_every=checkpoint_every,
                              resume=resume, consume=True)
    return state.global_params, hist


def comm_report(dcfg, method: str, n_params: int, steps: int, h: int,
                step_time_s: float, worker_speeds: Sequence[float],
                staleness: int = 0, faults=None) -> Dict:
    """Replay the run's sync schedule through the comm simulator: the
    symmetric fleet vs one with per-worker step clocks (``worker_speeds``
    are relative per-worker multipliers on the measured step seconds)."""
    import dataclasses
    from repro.core import make_strategy
    from repro.launch.comm_sim import (default_comm_model, simulate_gossip,
                                       simulate_heterogeneous,
                                       simulate_schedule)
    # mirror run_stage's clamping so the replayed schedule matches the
    # schedule the run actually executed
    delay = min(dcfg.sync_delay, h - 1)
    jitter = min(dcfg.h_jitter, h - 1 - delay)
    dcfg = dataclasses.replace(dcfg, h_inner_steps=h, sync_delay=delay,
                               h_jitter=jitter,
                               strategy=method if method != "hybrid"
                               else "diloco")
    strat = make_strategy(dcfg)
    events = strat.payload_schedule(n_params, steps, dcfg)
    comm = default_comm_model()
    homo = simulate_schedule(events, steps, step_time_s, comm)
    het = simulate_heterogeneous(
        events, steps, [step_time_s * m for m in worker_speeds], comm,
        staleness_steps=staleness, faults=faults)
    report = {"homogeneous": homo, "heterogeneous": het,
              "worker_speeds": list(worker_speeds),
              "step_time_s": step_time_s}
    if hasattr(strat, "gossip_rounds"):
        # gossip strategies synchronize per pair, not per fleet: replay the
        # actual pair dependencies so the wall-clock reflects pair barriers
        rounds = strat.gossip_rounds(n_params, steps, dcfg)
        report["gossip"] = simulate_gossip(
            rounds, steps, [step_time_s * m for m in worker_speeds], comm,
            staleness_steps=dcfg.staleness_bound, faults=faults)
    return report


def run_pipeline(method: str = "diloco", arch: str = "tiny",
                 reduced: bool = False, steps: Dict[str, int] = None,
                 workers: int = 4, per_worker_batch: int = 8,
                 seq_len: int = 128, adaptive_h: bool = False,
                 delta_dtype: str = "float32", grad_compress: str = "none",
                 drift_aware: bool = False,
                 sync_delay: int = 0, h_jitter: int = 0,
                 topology: str = "ring", staleness_bound: int = 0,
                 num_fragments: int = 4, error_feedback: bool = True,
                 worker_speeds: Sequence[float] = (),
                 prefetch: int = 0, fused_adamw: bool = False,
                 seed: int = 0, out_dir: Optional[str] = None,
                 eval_after_each_stage: bool = True,
                 fault_schedule: str = "", min_quorum: int = 1,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, resume: bool = False) -> Dict:
    """The full three-stage pipeline under one method.  Returns metrics.

    ``fault_schedule`` (a ``FaultSchedule.from_spec`` string or JSON path)
    injects scripted worker failures into the BASE stage — the long
    DiLoCo pretrain is where fleets churn; mid/SFT are short DDP-ish runs.
    ``checkpoint_dir``/``checkpoint_every``/``resume`` give the base stage
    crash-consistent auto-resume (a rerun with ``--resume`` continues
    bit-exactly from the last complete checkpoint)."""
    from repro.configs.base import DiLoCoConfig, OptimizerConfig
    from repro.core.schedule import AdaptiveH
    from repro.evals import chat_suite, heldout_metrics
    from repro.models.transformer import init_params
    from repro.serving import Engine

    steps = steps or {"base": 300, "mid": 120, "sft": 120}
    if worker_speeds and method != "ddp" and len(worker_speeds) != workers:
        raise ValueError(f"--worker-speeds needs one multiplier per worker: "
                         f"got {len(worker_speeds)} for {workers} workers")
    world, tok, stages, suites = build_pipeline(seq_len=seq_len, seed=seed)
    cfg, model = make_model(arch, reduced, tok.vocab_size)
    params, _ = init_params(cfg, jax.random.key(seed))

    total = sum(steps.values())
    opt_cfg = OptimizerConfig(total_steps=total, warmup_steps=20,
                              schedule="wsd", learning_rate=0.02,
                              adam_lr=1e-3, fused_adamw=fused_adamw)
    dcfg = DiLoCoConfig(num_workers=workers, delta_dtype=delta_dtype,
                        grad_compress=grad_compress,
                        drift_aware=drift_aware, sync_delay=sync_delay,
                        h_jitter=h_jitter, topology=topology,
                        staleness_bound=staleness_bound,
                        num_fragments=num_fragments,
                        error_feedback=error_feedback, sync_seed=seed)

    # paper §3: H=100 base, H=30 mid/SFT (scaled to our step budget: the
    # ratio sync-count/steps matches — base gets ~3 syncs, mid/sft ~4 each)
    h_by_stage = {"base": max(steps["base"] // 3, 1),
                  "mid": max(steps["mid"] // 4, 1),
                  "sft": max(steps["sft"] // 4, 1)}

    faults = None
    if fault_schedule:
        from repro.core import FaultSchedule
        faults = FaultSchedule.from_spec(fault_schedule)

    results: Dict = {"method": method, "arch": cfg.name, "stages": {}}
    for stage in ("base", "mid", "sft"):
        stage_method = method
        if method == "hybrid":
            stage_method = "diloco" if stage == "base" else "ddp"
        hs = AdaptiveH(h0=h_by_stage[stage]) if (
            adaptive_h and stage_method == "diloco") else None
        # faults + checkpoint/resume target the base stage: the long
        # decentralized pretrain is where workers churn and kills land
        is_base = stage == "base"
        params, hist = run_stage(
            stage_method, model, params, stages[stage],
            steps=steps[stage], workers=workers,
            per_worker_batch=per_worker_batch, h=h_by_stage[stage],
            opt_cfg=opt_cfg, diloco_cfg=dcfg, seed=seed, h_schedule=hs,
            prefetch=prefetch,
            faults=faults if is_base else None, min_quorum=min_quorum,
            checkpoint_dir=checkpoint_dir if is_base else None,
            checkpoint_every=checkpoint_every,
            resume=resume and is_base)
        entry = {"loss_first": hist["loss"][0], "loss_last": hist["loss"][-1],
                 "losses": hist["loss"][:: max(1, len(hist["loss"]) // 50)],
                 "method": stage_method,
                 "step_seconds": hist["step_seconds"]}
        for key in ("fault", "quorum", "quorum_skip", "rejoin_drift"):
            if hist.get(key):
                entry[key] = hist[key]
        if eval_after_each_stage:
            engine = Engine(model, params, tok)
            entry["core"] = heldout_metrics(ds=stages["base"], batches=4,
                                            batch_size=8, engine=engine)
            entry["tasks"] = chat_suite(engine, tok, suites)
        results["stages"][stage] = entry
        print(f"[{method}:{stage}] loss {entry['loss_first']:.3f} -> "
              f"{entry['loss_last']:.3f} "
              + (f"tasks={entry.get('tasks')}" if eval_after_each_stage else ""))

    if worker_speeds and method != "ddp":
        n_params = sum(int(x.size) for x in jax.tree.leaves(params))
        # staleness stays 0: the schedules' apply_step already carries the
        # strategy's overlap window (sync_delay) — adding it again would
        # double-count the hiding budget
        rep = comm_report(dcfg, method, n_params, steps["base"],
                          h_by_stage["base"],
                          results["stages"]["base"]["step_seconds"],
                          worker_speeds)
        results["comm_model"] = rep
        homo, het = rep["homogeneous"], rep["heterogeneous"]
        pair = ""
        if "gossip" in rep:
            # the fleet-barrier het number above is the worst case; the
            # per-pair replay is what the gossip runners actually pay
            pair = (f" pair-barrier wall="
                    f"{rep['gossip']['wall_clock_s']:.2f}s")
        print(f"[comm:{method}/{delta_dtype}] "
              f"bytes={homo['total_bytes']/1e6:.2f}MB/worker "
              f"homogeneous wall={homo['wall_clock_s']:.2f}s "
              f"heterogeneous wall={het['wall_clock_s']:.2f}s "
              f"(straggler adds {het['straggler_s']:.2f}s compute, "
              f"stall {het['stall_s']:.2f}s)" + pair)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        from repro.checkpoint import save_config, save_pytree
        ckpt = os.path.join(out_dir, f"{method}_final")
        save_pytree(params, ckpt)
        save_config(cfg, ckpt)   # so serve.py can rebuild the model
        with open(os.path.join(out_dir, f"{method}_metrics.json"), "w") as f:
            json.dump(results, f, indent=1, default=float)
    return results


def main(argv=None):
    from repro.core import strategy_names
    from repro.launch.compile_cache import setup_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--method",
                    choices=list(strategy_names()) + ["hybrid"],
                    default="diloco")
    ap.add_argument("--arch", type=str, default="tiny")
    ap.add_argument("--reduced", action="store_true",
                    help="build the 2-layer CPU-sized variant of --arch "
                         "instead of its published widths")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--adaptive-h", action="store_true")
    ap.add_argument("--sync-dtype", default=None,
                    choices=["f32", "bf16", "int8", "fp8", "e5m2",
                             "float32", "bfloat16", "fp8_e5m2"],
                    help="outer-sync wire codec (preferred spelling; "
                         "overrides --delta-dtype)")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8", "fp8", "fp8_e5m2"],
                    help="--method ddp only: compress the per-step update "
                         "exchange through this codec (K real workers + "
                         "error feedback, core.sync.CompressedDDPSync)")
    ap.add_argument("--delta-dtype", default="float32",
                    help="legacy spelling of --sync-dtype")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the lossy-codec error-feedback residual")
    ap.add_argument("--drift-aware", action="store_true")
    ap.add_argument("--sync-delay", type=int, default=0,
                    help="overlapped/pipelined: steps between delta capture "
                         "and apply")
    ap.add_argument("--h-jitter", type=int, default=0,
                    help="overlapped/async_gossip: max per-worker straggler "
                         "jitter on the sync period")
    ap.add_argument("--topology", default="ring",
                    choices=["ring", "random", "full"],
                    help="gossip/async_gossip: peer-matching topology "
                         "(full topology is exactly the DiLoCo mean)")
    ap.add_argument("--staleness-bound", type=int, default=0,
                    help="async_gossip: max staleness (in steps) of a peer "
                         "delta before it is dropped; 0 = synchronous pairs")
    ap.add_argument("--fragments", type=int, default=4,
                    help="streaming/pipelined: number of fragments F")
    ap.add_argument("--worker-speeds", type=str, default="",
                    help="comma list of per-worker relative step-time "
                         "multipliers (heterogeneous fleet); feeds the "
                         "post-run comm-simulator report")
    ap.add_argument("--prefetch", type=int, default=0,
                    help="assemble + device_put batches this many steps "
                         "ahead on a background thread (0 = synchronous)")
    ap.add_argument("--fused-adamw", action="store_true",
                    help="use the fused Pallas AdamW update kernel (same "
                         "update math as the unfused path)")
    ap.add_argument("--fault-schedule", type=str, default="",
                    help="scripted fault injection for the base stage: an "
                         "inline spec (crash:2@10,rejoin:2@40,kill@90) or a "
                         "JSON file path (repro.core.faults.FaultSchedule)")
    ap.add_argument("--min-quorum", type=int, default=1,
                    help="minimum live contributors for an outer round; "
                         "below it the round is skipped (workers keep "
                         "training locally)")
    ap.add_argument("--checkpoint-dir", type=str, default=None,
                    help="write crash-consistent checkpoints here at outer "
                         "boundaries (base stage)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="steps between checkpoints (0 = off)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the base stage from the latest complete "
                         "checkpoint in --checkpoint-dir (bit-exact "
                         "continuation)")
    ap.add_argument("--out-dir", type=str, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()
    canon = {"f32": "float32", "bf16": "bfloat16", "int8": "int8",
             "fp8": "fp8", "e5m2": "fp8_e5m2", "fp8_e5m2": "fp8_e5m2",
             "float32": "float32", "bfloat16": "bfloat16"}
    delta_dtype = canon[args.sync_dtype] if args.sync_dtype \
        else args.delta_dtype
    speeds = tuple(float(s) for s in args.worker_speeds.split(",") if s)
    run_pipeline(method=args.method, arch=args.arch, reduced=args.reduced,
                 steps={"base": args.steps, "mid": args.steps // 2,
                        "sft": args.steps // 2},
                 workers=args.workers, adaptive_h=args.adaptive_h,
                 delta_dtype=delta_dtype, grad_compress=args.grad_compress,
                 drift_aware=args.drift_aware,
                 sync_delay=args.sync_delay, h_jitter=args.h_jitter,
                 topology=args.topology,
                 staleness_bound=args.staleness_bound,
                 num_fragments=args.fragments,
                 error_feedback=not args.no_error_feedback,
                 worker_speeds=speeds, prefetch=args.prefetch,
                 fused_adamw=args.fused_adamw,
                 fault_schedule=args.fault_schedule,
                 min_quorum=args.min_quorum,
                 checkpoint_dir=args.checkpoint_dir,
                 checkpoint_every=args.checkpoint_every,
                 resume=args.resume,
                 seed=args.seed, out_dir=args.out_dir)


if __name__ == "__main__":
    main()
