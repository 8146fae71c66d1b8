"""Serving launcher: a request-stream driver over the continuous-batching
engine.  Loads a checkpoint (model config comes from the checkpoint's
``.cfg.json`` metadata, with ``--config <arch>`` as the fallback for
checkpoints that predate it), then answers chat-formatted requests.

  # one-shot prompts (stdin also works, one prompt per line)
  PYTHONPATH=src python -m repro.launch.serve --ckpt runs/diloco_final \
      --prompt "what is the color of ent3 ?" --temperature 0.7

  # timestamped request stream; reports per-request latency + tokens/s
  PYTHONPATH=src python -m repro.launch.serve --stream requests.jsonl --report

Stream files are JSONL: {"t": <arrival seconds>, "prompt": "...",
"max_new": N} — requests are admitted against the wall clock, so the report
reflects scheduling (admission/eviction/chunked prefill) under load, not
just raw decode speed.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np


def percentile(xs, q):
    """q-th percentile of a list, NaN when empty (shared with
    ``benchmarks.serving_bench``)."""
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def build_requests(args, tok):
    from repro.serving import Request
    stop = tok.special_id("<|assistant_end|>")
    items = []
    if args.stream:
        with open(args.stream) as f:
            for line in f:
                if line.strip():
                    d = json.loads(line)
                    items.append((float(d.get("t", 0.0)), d["prompt"],
                                  int(d.get("max_new", args.max_new))))
    else:
        prompts = args.prompt or [l.strip() for l in sys.stdin if l.strip()]
        items = [(0.0, p, args.max_new) for p in prompts]
    reqs = []
    for rid, (t, prompt, max_new) in enumerate(items):
        wrapped = (f"<|bos|><|user_start|>{prompt}<|user_end|>"
                   f"<|assistant_start|>")
        reqs.append((prompt, Request(
            rid=rid, prompt=tok.encode(wrapped), max_new=max_new,
            temperature=args.temperature if args.temperature > 0 else 1.0,
            greedy=args.temperature == 0.0, eos_id=stop, arrival=t)))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--config", type=str, default="tiny",
                    help="arch name fallback when the checkpoint has no "
                         ".cfg.json metadata")
    ap.add_argument("--reduced", action="store_true",
                    help="build the 2-layer CPU-sized variant of --config "
                         "instead of its published widths")
    ap.add_argument("--prompt", action="append", default=[])
    ap.add_argument("--stream", type=str, default=None,
                    help="JSONL request stream with arrival timestamps")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples at this temperature")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for fresh-init params "
                         "(ignored once --ckpt loads weights)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to K tokens per "
                         "slot per round via prompt-lookup (0 = off)")
    ap.add_argument("--policy",
                    choices=["fifo", "longest_prefill", "cache_aware"],
                    default="fifo")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV blocks across requests "
                         "via a radix tree: matched prefixes skip prefill "
                         "and reserve no pool budget (dense archs only)")
    ap.add_argument("--prefix-cache-blocks", type=int, default=None,
                    help="LRU bound on resident prefix-cache blocks "
                         "(default: bounded only by the pool)")
    ap.add_argument("--kv-dtype", type=str, default=None,
                    choices=["bf16", "f32", "int8", "fp8", "fp8_e5m2"],
                    help="KV-pool storage format override (default: the "
                         "checkpoint config's kv_cache_dtype, else the "
                         "compute dtype); int8/fp8 pools quantize on "
                         "append and halve-to-quarter pool bytes")
    ap.add_argument("--pool-bytes", type=int, default=None,
                    help="size the KV pool by device-byte budget instead "
                         "of slots x blocks (quantized pools fit more "
                         "blocks, admitting more concurrent requests)")
    ap.add_argument("--report", action="store_true",
                    help="print per-request latency + aggregate tokens/s")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from repro.checkpoint import load_config
    from repro.launch.train import build_pipeline, make_model
    from repro.models import build_model
    from repro.models.transformer import init_params
    from repro.serving import Engine

    world, tok, stages, suites = build_pipeline()
    cfg = load_config(args.ckpt) if args.ckpt else None
    if cfg is not None:
        print(f"# model config from checkpoint metadata: {cfg.name}")
    else:
        cfg, _ = make_model(args.config, args.reduced, tok.vocab_size)
    if args.kv_dtype is not None:
        # the pool format is a serving decision: override whatever the
        # checkpoint metadata says BEFORE the engine reads model.cfg
        cfg = cfg.with_(kv_cache_dtype=args.kv_dtype
                        if args.kv_dtype != "f32" else "float32")
    model = build_model(cfg)
    if cfg.vocab_size < tok.vocab_size:
        print(f"# warning: model vocab {cfg.vocab_size} < pipeline "
              f"tokenizer vocab {tok.vocab_size}", file=sys.stderr)
    params, _ = init_params(cfg, jax.random.key(args.seed))
    if args.ckpt:
        from repro.checkpoint import load_pytree
        params = load_pytree(params, args.ckpt)

    engine = Engine(model, params, tok, max_len=args.max_len,
                    num_slots=args.slots, block_size=args.block_size,
                    policy=args.policy, spec_k=args.spec_k,
                    pool_bytes=args.pool_bytes,
                    prefix_cache=args.prefix_cache,
                    prefix_cache_blocks=args.prefix_cache_blocks)
    reqs = build_requests(args, tok)
    if not reqs:
        print("no requests", file=sys.stderr)
        return

    if engine.continuous:
        stats = engine.run([r for _, r in reqs], use_time=True)
        for prompt, r in reqs:
            row = r.tokens
            if r.eos_id in row:
                row = row[:row.index(r.eos_id)]
            print(f">>> {prompt}\n{tok.decode(row).strip()}")
    else:   # ssm/hybrid fallback: static buckets, grouped by max_new (the
            # already-encoded prompt ids go straight through — no lossy
            # decode/re-encode round-trip)
        rows = [None] * len(reqs)
        by_mn = {}
        for i, (_, r) in enumerate(reqs):
            by_mn.setdefault(r.max_new, []).append(i)
        for mn, idxs in by_mn.items():
            out = engine.generate(
                [reqs[i][1].prompt for i in idxs], max_new=mn,
                greedy=args.temperature == 0.0,
                temperature=args.temperature or 1.0,
                eos_id=reqs[idxs[0]][1].eos_id)
            for i, row in zip(idxs, out):
                rows[i] = list(row)
        for (prompt, r), row in zip(reqs, rows):
            if r.eos_id in row:
                row = row[:row.index(r.eos_id)]
            print(f">>> {prompt}\n{tok.decode(row).strip()}")
        stats = None
        if args.report:
            print("# report unavailable on the static fallback path "
                  "(ssm/hybrid arch): arrival times and per-request "
                  "latency are not modeled", file=sys.stderr)

    if args.report and stats is not None:
        from repro.kernels.common import pallas_mode
        lats = [r.finish_time - r.arrival for _, r in reqs
                if r.finish_time is not None]
        # time-to-first-token: the per-request latency prefix sharing
        # actually moves (a cache hit skips the matched prefill outright)
        ttfts = [r.ttft for _, r in reqs if r.first_token_time is not None]
        print(f"# requests={len(reqs)} generated={stats['generated']} "
              f"step_calls={stats['step_calls']} "
              f"prefill_tokens={stats['prefill_tokens']}")
        print(f"# wall={stats['wall']:.3f}s "
              f"tokens_per_s={stats['generated'] / stats['wall']:.1f} "
              f"latency_p50={percentile(lats, 50):.3f}s "
              f"latency_p95={percentile(lats, 95):.3f}s "
              f"ttft_p50={percentile(ttfts, 50):.3f}s "
              f"ttft_p95={percentile(ttfts, 95):.3f}s")
        if "prefix" in stats:
            p = stats["prefix"]
            print(f"# prefix_cache hit_rate={p['hit_rate']:.2f} "
                  f"matched_tokens={p['matched_tokens']} "
                  f"(matched_frac={p['matched_frac']:.2f}) "
                  f"shared_blocks={p['resident_blocks']} "
                  f"forked={p['forked']} "
                  f"bytes_saved={p['bytes_saved']} "
                  f"skipped_prefill_tokens={stats['prefix_skipped_tokens']}")
        if args.spec_k > 0:
            # per-request accept rates: p50/p95 over requests that drafted
            rates = [r.accept_rate for _, r in reqs if r.drafted]
            print(f"# spec_k={args.spec_k} drafted={stats['drafted']} "
                  f"accepted={stats['accepted']} "
                  f"accept_rate={stats['accept_rate']:.3f} "
                  f"accept_rate_p50={percentile(rates, 50):.3f} "
                  f"accept_rate_p95={percentile(rates, 95):.3f} "
                  f"rolled_back={stats['rolled_back']}")
        if stats.get("recycled_blocks"):
            print(f"# window_recycled_blocks={stats['recycled_blocks']}")
        kv = engine.kv_report()
        print(f"# kv_dtype={kv['kv_cache_dtype']} "
              f"(pool {kv['kv_pool_dtype']}) "
              f"bytes_per_block={kv['bytes_per_block']} "
              f"num_blocks={kv['num_blocks']} "
              f"pool_bytes={kv['pool_bytes']} "
              f"peak_admitted={stats['peak_admitted']}")
        print(f"# attn_impl={engine.attn_impl} pallas_mode={pallas_mode()} "
              f"policy={engine.policy}")


if __name__ == "__main__":
    main()
