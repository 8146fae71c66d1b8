"""Chip smoke test: nanochat-d20 at its published widths, end to end on one
TPU chip, through the library calls the CLIs make.

    python chip_smoke.py [--seed N]

Phases, all in this one process (it is the only one that holds the chip):

* device  — refuse anything but a TPU backend;
* train   — ``repro.launch.train.run_stage`` -> ``DistTrainer`` on
  synthetic packed data (seq 2048): a few chunked steps under ``ddp``,
  then under ``diloco`` with K=1 and H=2, so outer syncs execute; every
  loss must be finite and the first one near ln(vocab);
* serve   — ``repro.serving.Engine`` over random-init params with the
  attention kernels auto-selected (compiled Pallas paged kernels): 8
  requests of 64-1024 prompt tokens and 32 new tokens each, once with
  ``spec_k=0`` and once with ``spec_k=4``; every greedy first token must
  be a (near-)argmax of a full-sequence reference forward;
* kernels — the compiled paged decode / verify kernels (f32, bf16, int8
  and fp8 pools, and fp8 QK^T tiles) against their ``ref.py`` oracles at
  d20 shapes, and the quantize kernels against theirs on a d20 leaf.

Lines starting with ``smoke:`` are smoke observations (wall seconds per
phase, ``peak_bytes_in_use``, the Pallas mode), not benchmark metrics.
The last line is ``{"ok": true, "device": {...}}``; any failure exits
non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ARCH = "nanochat-d20"
SEQ = 2048               # training sequence length
BATCH = 1                # sequences per worker per step (see CHANGES.md)
LOSS_CHUNK = 512         # chunked CE: never materialize (B, S, V) logits
H = 2                    # DiLoCo inner steps per outer sync
SLOTS, MAX_LEN, BLOCK = 8, 2048, 16
POOL_BLOCKS = SLOTS * 72     # 1152 tokens per slot: the longest request
                             # (1024 + 32) plus slack
N_REQ, MAX_NEW = 8, 32
LOGIT_TOL = 0.25         # greedy pick within this of the reference max
ATTN_TOL = 2e-2          # |paged kernel - oracle| (bf16 MXU passes)
FP8_QK_TOL = 5e-2        # fp8 QK^T tiles: one e4m3 rounding step (2^-3
                         # relative) may resolve a tie differently


class Fail(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Fail(msg)


def report(phase, **obs):
    print("smoke: " + json.dumps({"phase": phase, **obs}), flush=True)


class Phase:
    """Wall seconds and the device's peak bytes of one phase."""

    def __init__(self, name, dev):
        self.name, self.dev = name, dev

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.obs = {}
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            from repro.kernels.common import pallas_mode
            stats = self.dev.memory_stats() or {}
            report(self.name, wall_s=time.perf_counter() - self.t0,
                   peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                   pallas_mode=pallas_mode(), **self.obs)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(method, model, params, ds, seed, dev):
    """A warm-up stage (compiles the chunk and outer programs, which the
    persistent cache then holds), then a timed stage.  Returns the
    trained params and every loss."""
    import jax
    from repro.configs.base import DiLoCoConfig, OptimizerConfig
    from repro.launch.train import run_stage

    opt = OptimizerConfig(total_steps=100, warmup_steps=2)
    dcfg = DiLoCoConfig(num_workers=1, sync_seed=seed)
    losses, syncs = [], 0
    # a ddp chunk spans the whole stage, so both stages take H steps to
    # share one compiled chunk; diloco chunks end at every outer sync
    timed = H if method == "ddp" else 2 * H
    for tag, steps in (("warm", H), ("timed", timed)):
        with Phase(f"train_{method}_{tag}", dev) as ph:
            params, hist = run_stage(method, model, params, ds, steps=steps,
                                     workers=1, per_worker_batch=BATCH, h=H,
                                     opt_cfg=opt, diloco_cfg=dcfg, seed=seed)
            jax.block_until_ready(params)
            ph.obs = {"steps": steps, "tokens_per_step": BATCH * SEQ,
                      "loss": hist["loss"],
                      "outer_syncs": len(hist["sync_steps"])
                      if method == "diloco" else 0}
        losses += hist["loss"]
        syncs += ph.obs["outer_syncs"]
    check(all(math.isfinite(x) for x in losses),
          f"{method}: non-finite loss in {losses}")
    if method == "diloco":
        check(syncs >= 1, "diloco ran no outer sync")
    return params, losses


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(tokens, vocab, seed):
    """8 greedy requests whose prompts are windows of the packed corpus,
    64-1024 tokens long (both ends included)."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(64, 1025, size=N_REQ)
    lens[0], lens[1] = 64, 1024
    reqs = []
    for i, n in enumerate(lens):
        s = int(rng.integers(0, len(tokens) - n))
        prompt = [int(t) % vocab for t in tokens[s:s + n]]
        reqs.append(Request(rid=i, prompt=prompt, max_new=MAX_NEW))
    return reqs


def reference_last_logits(model, params, prompts):
    """f32 full-precision logits at each prompt's last position from the
    full-sequence training forward (jnp attention, no paged cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.layers import unembed
    from repro.models.transformer import forward_hidden
    cfg = model.cfg
    L = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), L), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p          # right padding: causal, never seen
    last = jnp.asarray([len(p) - 1 for p in prompts])

    @jax.jit
    def f(params, toks):
        h, _ = forward_hidden(params, {"tokens": toks}, cfg)
        h_last = h[jnp.arange(h.shape[0]), last]
        return unembed(params["embed"], h_last, cfg).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(f(params, jnp.asarray(toks)))


def serve_phase(model, tokens, seed, dev):
    import jax
    import numpy as np
    from repro.models.transformer import init_params
    from repro.serving import Engine

    cfg = model.cfg
    params, _ = init_params(cfg, jax.random.key(seed + 1))
    prompts = [r.prompt for r in make_requests(tokens, cfg.vocab_size, seed)]
    with Phase("serve_reference", dev):
        ref = reference_last_logits(model, params, prompts)
    check(np.isfinite(ref).all(), "reference logits are not finite")
    outs = {}
    for k in (0, 4):
        with Phase(f"serve_spec{k}", dev) as ph:
            eng = Engine(model, params, max_len=MAX_LEN, num_slots=SLOTS,
                         block_size=BLOCK, num_blocks=POOL_BLOCKS, spec_k=k)
            check(eng.attn_impl == "pallas",
                  f"engine picked attention {eng.attn_impl!r}, not pallas")
            reqs = make_requests(tokens, cfg.vocab_size, seed)
            stats = eng.run(reqs)
            gaps = [float(ref[i].max() - ref[i][r.tokens[0]])
                    for i, r in enumerate(reqs)]
            ph.obs = {"requests": len(reqs),
                      "prompt_tokens": [len(r.prompt) for r in reqs],
                      "generated": stats["generated"],
                      "step_calls": stats["step_calls"],
                      "first_token_logit_gap": gaps,
                      "attn_impl": eng.attn_impl}
            del eng             # frees the KV pool before the next engine
        for r in reqs:
            check(len(r.tokens) == MAX_NEW,
                  f"spec_k={k}: request {r.rid} got {len(r.tokens)} tokens")
            check(all(0 <= t < cfg.vocab_size for t in r.tokens),
                  f"spec_k={k}: token id out of range")
        check(max(gaps) <= LOGIT_TOL,
              f"spec_k={k}: a greedy first token is {max(gaps):.3f} below "
              f"the reference max logit (tolerance {LOGIT_TOL})")
        outs[k] = [r.tokens for r in reqs]
    same = sum(a == b for a, b in zip(outs[0], outs[4]))
    report("serve_agreement", spec4_equals_spec0_requests=same,
           of=len(outs[0]))


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def paged_case(key, fmt, T):
    """d20 shapes: 8 slots, 10 KV heads of 128, 16-token blocks, 2048
    tokens per slot, shuffled block tables with ragged mapped prefixes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    S, KV, G, D, bs, MB = SLOTS, 10, 1, 128, BLOCK, MAX_LEN // BLOCK
    NB = S * MB
    ks = jax.random.split(key, 3)
    qshape = (S, T, KV, G, D)
    q = jax.random.normal(ks[0], qshape, jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, KV, D), jnp.float32)
    rng = np.random.default_rng(0)
    tables = np.full((S, MB), -1, np.int32)
    perm = rng.permutation(NB)
    start = np.zeros((S,), np.int32)
    off = 0
    for s in range(S):
        n = int(rng.integers(1, MB + 1))
        tables[s, :n] = perm[off:off + n]
        off += n
        start[s] = int(rng.integers(0, n * bs - T + 1))
    if fmt == "bfloat16":
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(start)


def kernel_phase(dev):
    import jax
    import jax.numpy as jnp
    from repro.kernels import decode_attention as da
    from repro.kernels.quantize import (dequantize, quantize_ef,
                                        reference_dequantize,
                                        reference_quantize_axis,
                                        reference_quantize_ef)

    errs = {}
    with Phase("kernels", dev) as ph:
        # pool format -> (kernel suffix, oracle suffix); fp8_qk is an f32
        # pool with fp8 QK^T tiles (ModelConfig.fp8_matmul)
        formats = {"float32": ("", ""), "bfloat16": ("", ""),
                   "int8": ("_dequant", "_dequant"),
                   "fp8_e4m3": ("_dequant", "_dequant"),
                   "fp8_qk": ("", "_fp8")}
        # built once per oracle name, not per call
        oracles = {n: jax.jit(getattr(da, n))  # replint: disable=retrace-hazard
                   for n in dir(da) if n.startswith("reference_paged_")}
        for fmt, (ksfx, rsfx) in formats.items():
            for kind, T in (("decode", 1), ("verify", 5)):
                q, kp, vp, tab, start = paged_case(
                    jax.random.key(T), fmt, T)
                pools = (kp, vp)
                if ksfx:
                    kq, ks = reference_quantize_axis(kp, -1, fmt)
                    vq, vs = reference_quantize_axis(vp, -1, fmt)
                    pools = (kq, vq, ks[..., 0], vs[..., 0])
                args = (q[:, 0], *pools, tab, start) if T == 1 else (
                    q, *pools, tab, start, jnp.full(start.shape, T,
                                                    jnp.int32))
                kern = getattr(da, f"paged_{kind}_attention{ksfx}")
                extra = {"fp8": True} if fmt == "fp8_qk" else {}
                ref_fn = oracles[f"reference_paged_{kind}_attention{rsfx}"]
                out = kern(*args, **extra)
                with jax.default_matmul_precision("highest"):
                    ref = ref_fn(*args)
                errs[f"paged_{kind}_{fmt}"] = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - ref.astype(jnp.float32))))
        # quantize: the d20 MLP matrix, K = 1 and 4 worker rows
        ref_q = jax.jit(reference_quantize_ef)
        ref_dq = jax.jit(reference_dequantize)
        for K in (1, 4):
            kx, kr = jax.random.split(jax.random.key(K))
            x = jax.random.normal(kx, (K, 1280, 5120)) * 0.05
            r = jax.random.normal(kr, (K, 1280, 5120)) * 0.005
            qk, nr, s = quantize_ef(x, r, dtype="int8")
            qr, nrr, sr = ref_q(x, r)
            dq = dequantize(qk, s)
            dqr = ref_dq(qr, sr)
            errs[f"quantize_K{K}_scale"] = float(jnp.max(jnp.abs(s - sr)))
            errs[f"quantize_K{K}_payload_mismatch"] = int(
                jnp.sum(qk != qr))
            errs[f"quantize_K{K}_levels"] = float(
                jnp.max(jnp.abs(dq - dqr) / sr))
            errs[f"quantize_K{K}_residual"] = float(
                jnp.max(jnp.abs(nr - nrr) / sr))
        ph.obs = {"errors": errs, "attn_tol": ATTN_TOL,
                  "fp8_qk_tol": FP8_QK_TOL}
    for name, err in errs.items():
        if name.startswith("paged_"):
            tol = FP8_QK_TOL if name.endswith("fp8_qk") else ATTN_TOL
            check(err <= tol, f"{name}: |kernel - oracle| = {err}")
    for K in (1, 4):
        check(errs[f"quantize_K{K}_scale"] == 0.0,
              f"quantize K={K}: scales differ from the oracle")
        check(errs[f"quantize_K{K}_levels"] <= 1.0
              and errs[f"quantize_K{K}_residual"] <= 1.0,
              f"quantize K={K}: payload or residual off by more than one "
              f"quantization level")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the synthetic corpus and the "
                         "request mix")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import setup_compile_cache
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()))
    cache = setup_compile_cache()
    from repro.configs import get_config
    from repro.launch.train import build_pipeline, make_model
    from repro.models import build_model
    from repro.models.transformer import init_params

    _, tok, stages, _ = build_pipeline(seq_len=SEQ, seed=args.seed)
    cfg, _ = make_model(ARCH, False, tok.vocab_size)
    check(cfg == get_config(ARCH),
          "make_model did not keep the published config")
    cfg = cfg.with_(loss_chunk=LOSS_CHUNK)
    model = build_model(cfg)
    report("config", arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, heads=cfg.num_heads,
           kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim, d_ff=cfg.d_ff,
           mlp=cfg.mlp_activation, vocab=cfg.vocab_size,
           params=cfg.param_count(), seq=SEQ, batch=BATCH,
           compile_cache=cache)

    params, _ = init_params(cfg, jax.random.key(args.seed))
    params, ddp_losses = train_phase("ddp", model, params, stages["base"],
                                     args.seed, dev)
    first, ln_v = ddp_losses[0], math.log(cfg.vocab_size)
    check(abs(first - ln_v) < 1.0,
          f"first loss {first:.3f} is not near ln(vocab) = {ln_v:.3f}")
    params, _ = train_phase("diloco", model, params, stages["base"],
                            args.seed, dev)
    del params

    serve_phase(model, stages["base"].tokens, args.seed, dev)
    kernel_phase(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Fail as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
