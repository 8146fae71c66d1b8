"""Driver for ``train`` traffic: the program's ``DistTrainer`` under the
strategy the traffic file names, fed seeded token rows.

One trainer object and one state serve the whole run, and every call is
``trainer.run`` as the program's launcher makes it (``consume=True``, the
default chunking: one scanned chunk per round of H inner steps):

1. set-up makes the weights, builds the trainer and drives it through its
   first round with one call of H steps, which ends in the strategy's
   outer step; it reads the round's losses, the optimizer's first moments
   and the parameters' change;
2. the same state goes into one more ``trainer.run`` call, the window: its
   first round traces (and compiles or loads) the call's own programs and
   is set-up; the window opens when that round's outer step has finished
   on the device and closes at the first round boundary after
   ``seconds``, once all work sent before it has finished.  The data
   callback marks the boundaries and ends the call when the window
   closes;
3. after the window the state is freed and the reference follows the same
   first round on the same rows (``reference.py``).
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import flops
import gen
import harness
import reference
import weights

MUON_KEY, ADAM_KEY = "muon", "adamw"


class WindowClosed(Exception):
    pass


def optimizer_config(o: Dict):
    from repro.configs.base import OptimizerConfig

    return OptimizerConfig(**{**o, "adam_betas": tuple(o["adam_betas"])})


def diloco_config(t: Dict):
    from repro.configs.base import DiLoCoConfig

    return DiLoCoConfig(num_workers=t["workers"], h_inner_steps=t["h"],
                        strategy=t["strategy"], delta_dtype=t["delta_dtype"],
                        **t["outer"])


def moment_norms(inner_opt) -> Dict[str, float]:
    """Per-leaf norms of worker 0's first moment: Muon's momentum for a
    weight matrix, AdamW's ``m`` for the rest (the program keeps an empty
    placeholder in the slot a leaf does not use)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(mu, m):
        def leaf(a, b):
            x = a[0] if a.size else b[0]
            return jnp.sqrt(jnp.sum(jnp.square(x)))
        return jax.tree.map(leaf, mu, m)

    return _host(norms(inner_opt[MUON_KEY]["mu"], inner_opt[ADAM_KEY]["m"]))


def _host(tree) -> Dict[str, float]:
    import jax

    return {jax.tree_util.keystr(p): float(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(params, m: Dict, seed: int) -> Dict[str, float]:
    """Per-leaf norms of ``params`` minus the seed's initial weights."""
    import jax

    init = weights.make(m, gen.jax_seed(seed, 0))
    f = jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))
    out = {k: float(v) for k, v in f(params, init).items()}
    del init
    return out


def settle() -> None:
    """Wait until every computation sent to the device has finished: the
    outer step the program dispatches at a round boundary is still running
    when the next round's data is asked for."""
    import jax

    for a in jax.live_arrays():
        if not a.is_deleted():
            a.block_until_ready()


class Feed:
    """The program's data callback: seeded rows by global step, plus an
    optional hook called with the run-local step first."""

    def __init__(self, seed: int, workers: int, batch: int, seq: int,
                 vocab: int):
        self.args = (seed, workers, batch, seq, vocab)
        self.offset = 0
        self.hook = None

    def __call__(self, step: int):
        if self.hook is not None:
            self.hook(step)
        seed, k, b, s, v = self.args
        return gen.train_batch(seed, self.offset + step, k, b, s, v)


def start(cell, seed: int):
    """Set-up up to the window: weights, trainer, and the first round
    through ``trainer.run``.  Returns the trainer, its state, the feed and
    the program's readings of that round."""
    from repro.core import DistTrainer, make_strategy
    from repro.models import build_model

    conf, t = cell.config, cell.traffic
    m = conf["run_as"]
    B, S, K, H = conf["train"]["batch"], conf["train"]["seq"], \
        t["workers"], t["h"]
    cfg = harness.model_config(conf)
    model = build_model(cfg)
    harness.check_layout(cfg, weights.spec(m))
    dcfg = diloco_config(t)
    trainer = DistTrainer(model.loss, optimizer_config(t["optimizer"]), dcfg,
                          make_strategy(dcfg))
    state = trainer.init(weights.make(m, gen.jax_seed(seed, 0)))
    feed = Feed(seed, K, B, S, m["vocab_size"])
    state, hist = trainer.run(state, feed, H, consume=True)
    feed.offset = H
    return trainer, state, feed, {
        "loss": list(hist["loss"]),
        "moments": moment_norms(state.inner_opt),
        "change": change_norms(state.global_params, m, seed)}


def run(cell, seed: int, seconds: float, trace_dir, t_start: float) -> Dict:
    import jax

    conf, t = cell.config, cell.traffic
    m = conf["run_as"]
    B, S, K, H = conf["train"]["batch"], conf["train"]["seq"], \
        t["workers"], t["h"]
    trainer, state, feed, prog = start(cell, seed)

    # -- the window: whole rounds of one more call -------------------------
    mark: Dict[str, float] = {}
    rounds_traced = t.get("trace_rounds", 2)

    def hook(step: int) -> None:
        if step == H:
            settle()
            mark["open"] = time.perf_counter()
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
                mark["trace_open"] = time.perf_counter()
            return
        if step <= H or step % H or "open" not in mark:
            return
        done = (step - H) // H >= rounds_traced if trace_dir \
            else time.perf_counter() - mark["open"] >= seconds
        if done:
            settle()
            mark["close"], mark["steps"] = time.perf_counter(), step - H
            raise WindowClosed

    feed.hook = hook
    try:
        trainer.run(state, feed, 1 << 40, consume=True)
    except WindowClosed:
        pass
    del state
    if trace_dir:
        jax.profiler.stop_trace()
    peak_bytes = harness.memory_peak_bytes()
    gc.collect()

    window_s = mark["close"] - mark["open"]
    tokens = mark["steps"] * K * B * S
    out = {
        "setup_s": mark["open"] - t_start,
        "window_s": window_s,
        "tokens": tokens,
        "steps": mark["steps"],
        "rounds": mark["steps"] // H,
        "train_tokens_per_s": tokens / window_s,
        "flops_per_token": flops.train_flops_per_token(m, S),
        "memory_peak_bytes": peak_bytes,
        "attempted": mark["steps"],
        "failed": 0,
    }
    if trace_dir:
        out["trace_window_s"] = mark["close"] - mark["trace_open"]

    ref = follow_reference(m, t, B, S, seed)
    out["numbers"] = compare(prog, ref)
    return out


def follow_reference(m: Dict, t: Dict, B: int, S: int, seed: int,
                     dtype: str = "float32", fault: str = "") -> Dict:
    """The reference over the same first round and rows: H inner steps per
    worker, then the outer step.

    ``fault`` plants one of the faults a training cell can have, for
    reading how far it moves the compared numbers: ``half_batch`` takes
    the loss over the first half of each row's positions (B = 1: half of
    the batch's tokens), ``no_outer`` leaves the outer step out."""
    import jax
    import jax.numpy as jnp

    o, K, H = t["optimizer"], t["workers"], t["h"]
    cut = S // 2 if fault == "half_batch" else S
    step = reference.make_train_step(m, o, min(512, cut), dtype)
    mean_w = jax.jit(lambda *ws: jax.tree.map(
        lambda *x: sum(x) / len(x), *ws))
    outer = jax.jit(lambda a, w, v: reference.outer_update(t["outer"], a, w,
                                                           v),
                    donate_argnums=(0, 2))
    anchor = weights.make(m, gen.jax_seed(seed, 0))
    vel = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), anchor)
    workers = [jax.tree.map(jnp.copy, anchor) for _ in range(K)]
    states = [reference.opt_init(anchor) for _ in range(K)]
    losses, grads = [], None
    with reference.precision():
        for g in range(H):
            b = gen.train_batch(seed, g, K, B, S, m["vocab_size"])
            row = []
            for w in range(K):
                workers[w], states[w], loss, gn = step(
                    workers[w], states[w], b["tokens"][w][:, :cut],
                    b["labels"][w][:, :cut], g)
                row.append(float(loss))
                if grads is None and w == 0:
                    grads = {k: float(v) for k, v in gn.items()}
            losses.append(sum(row) / K)
        moments = {k: float(v) for k, v in
                   reference.moment_norms(states[0]).items()}
        if fault == "no_outer":
            anchor = workers[0]
        else:
            anchor, vel = outer(anchor, mean_w(*workers), vel)
    del workers, states, vel
    change = change_norms(anchor, m, seed)
    return {"loss": losses, "grads": grads, "moments": moments,
            "change": change}


def compare(prog: Dict, ref: Dict, floor: float = 1e-3) -> Dict:
    """The numbers ``correct`` compares:

    * ``loss_gap``   — the widest gap between the program's and the
      reference's loss over the round's steps;
    * ``moment_gap`` — over leaves, the widest gap between the norms of
      the optimizer's first moment after the round (Muon's momentum,
      AdamW's ``m``), each against the reference's norm of that leaf or
      of the median leaf, whichever is larger;
    * ``change_gap`` — the same for the parameters' change after the
      round's outer step, over the leaves whose first reference gradient
      is at least ``floor`` times the median leaf's (a leaf below it, like
      a key bias under softmax, moves by round-off alone under AdamW).
    """
    import numpy as np

    def worst(prog, refn, keys):
        med = float(np.median([refn[k] for k in keys]))
        return max(abs(prog[k] - refn[k]) / max(refn[k], med, 1e-30)
                   for k in keys)

    losses, rg = prog["loss"], ref["grads"]
    gmed = float(np.median(list(rg.values())))
    moved = [k for k in rg if rg[k] >= floor * gmed]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref["loss"]))
        if len(losses) == len(ref["loss"]) else float("inf"),
        "moment_gap": worst(prog["moments"], ref["moments"],
                            sorted(ref["moments"])),
        "change_gap": worst(prog["change"], ref["change"], moved),
    }
