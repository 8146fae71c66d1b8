"""The plain reference: a dense pre-norm decoder, its loss and gradients,
nanochat's optimizer split (Muon for weight matrices, AdamW for the rest)
and the DiLoCo outer step, in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision.  It imports nothing of the program and reads
its sizes and hyper-parameters from the benchmark's own files.

Layer by layer (``jax.checkpoint`` around each, which changes no
arithmetic), so the backward pass fits beside the optimizer state.

``dtype="fp8"`` is the precision control: float32, but both operands of
every projection, MLP and LM-head matmul, in the forward pass and in the
two matmuls of its backward pass, rounded to float8_e4m3 under a
per-tensor scale, one step below the single bfloat16 pass that a float32
matmul at default precision makes on a TPU.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

import flops

_NS = (3.4445, -4.7750, 2.0315)          # quintic Newton-Schulz (Muon)
_ADAM_KEYS = ("table", "unembed", "scale", "bq", "bk", "bv")


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotary embedding on (S, H, D), rotating the two halves of D."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


def _q8(x):
    """Round to float8_e4m3 under a per-tensor amax scale, back to x's
    type."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


@jax.custom_vjp
def _mm8(x, w):
    return _q8(x) @ _q8(w)


def _mm8_fwd(x, w):
    xq, wq = _q8(x), _q8(w)
    return xq @ wq, (xq, wq)


def _mm8_bwd(res, g):
    # the backward matmuls take fp8 operands too, the cotangent under its
    # own scale (left to autodiff, the cast would round it unscaled)
    xq, wq = res
    gq = _q8(g)
    return gq @ wq.T, jnp.einsum("...i,...j->ij", xq, gq)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def matmul(dtype: str = "float32"):
    if dtype == "fp8":
        return _mm8
    return lambda x, w: x @ w


def _layer(m: Dict, h, lp, pos, mm):
    """One block over a (S, d) sequence, causal attention."""
    S = h.shape[0]
    hd = flops.head_dim(m)
    H, KV = m["num_heads"], m["num_kv_heads"]
    eps = m["norm_eps"]
    a = lp["attn"]
    x = _rms(h, lp["ln1"]["scale"], eps)
    q, k, v = mm(x, a["wq"]), mm(x, a["wk"]), mm(x, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(S, H, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(S, KV, hd), pos, m["rope_theta"])
    v = v.reshape(S, KV, hd)
    rep = H // KV
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k).astype(jnp.float32) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    h = h + mm(o, a["wo"])
    x = _rms(h, lp["ln2"]["scale"], eps)
    mp = lp["mlp"]
    if m["mlp_activation"] == "swiglu":
        y = jax.nn.silu(mm(x, mp["w_gate"])) * mm(x, mp["w_up"])
    elif m["mlp_activation"] == "relu2":
        y = jnp.square(jax.nn.relu(mm(x, mp["w_up"])))
    else:
        raise ValueError(m["mlp_activation"])
    return h + mm(y, mp["w_down"])


def hidden(m: Dict, params, tokens, mm=matmul()):
    """(S,) token ids -> (S, d) final-normed hidden states."""
    pos = jnp.arange(tokens.shape[0])
    h = params["embed"]["table"][tokens]

    @jax.checkpoint
    def body(h, lp):
        return _layer(m, h, lp, pos, mm), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return _rms(h, params["final_norm"]["scale"], m["norm_eps"])


def head(m: Dict, params):
    e = params["embed"]
    return e["table"].T if m["tie_embeddings"] else e["unembed"]


def loss(m: Dict, params, tokens, labels, chunk: int = 512, mm=matmul()):
    """Mean next-token cross-entropy of one (B, S) batch, the vocab
    projection taken ``chunk`` positions at a time."""
    w = head(m, params)

    def one(t, lab):
        h = hidden(m, params, t, mm)
        n = h.shape[0] // chunk

        @jax.checkpoint
        def ce(acc, xs):
            hc, lc = xs
            z = mm(hc, w).astype(jnp.float32)
            lse = jax.nn.logsumexp(z, -1)
            gold = jnp.take_along_axis(z, lc[:, None], -1)[:, 0]
            return acc + jnp.sum(lse - gold), None

        tot, _ = jax.lax.scan(ce, jnp.zeros((), jnp.float32),
                              (h.reshape(n, chunk, -1),
                               lab.reshape(n, chunk)))
        return tot / h.shape[0]

    return jnp.mean(jax.vmap(one)(tokens, labels))


# ---------------------------------------------------------------------------
# optimizer: global-norm clip, Muon on weight matrices, AdamW elsewhere
# ---------------------------------------------------------------------------

def is_adam(path) -> bool:
    keys = [getattr(p, "key", "") for p in path]
    return any(k in _ADAM_KEYS for k in keys)


def lr_at(base: float, step, o: Dict):
    """Warm-up to ``base`` over ``warmup_steps``, then warmup-stable-decay."""
    s = jnp.asarray(step, jnp.float32)
    warm = max(o["warmup_steps"], 1)
    total = max(o["total_steps"], 1)
    decay0 = 0.8 * total
    frac = jnp.clip((s - decay0) / max(total - decay0, 1), 0.0, 1.0)
    main = base * (1.0 - (1.0 - o["final_lr_frac"]) * frac)
    return jnp.where(s < o["warmup_steps"],
                     base * jnp.minimum(1.0, (s + 1.0) / warm), main)


def orthogonalize(g, steps: int):
    """Quintic Newton-Schulz over the last two dims (Muon)."""
    a, b, c = _NS
    x = g.astype(jnp.float32)
    wide = x.shape[-2] > x.shape[-1]
    if wide:
        x = jnp.swapaxes(x, -1, -2)
    x = x / (jnp.sqrt(jnp.sum(x * x, axis=(-2, -1), keepdims=True)) + 1e-7)
    for _ in range(steps):
        A = x @ jnp.swapaxes(x, -1, -2)
        x = a * x + (b * A + c * A @ A) @ x
    return jnp.swapaxes(x, -1, -2) if wide else x


def _uses_adam(path, p) -> bool:
    return is_adam(path) or p.ndim < 2


def opt_init(params):
    """Muon momentum for weight matrices, AdamW moments for the rest; a
    leaf's other slots hold empty placeholders."""
    def z(want_adam):
        return lambda path, p: jnp.zeros(
            p.shape if _uses_adam(path, p) == want_adam else (0,),
            jnp.float32)
    mk = jax.tree_util.tree_map_with_path
    return {"mu": mk(z(False), params), "m": mk(z(True), params),
            "v": mk(z(True), params)}


def clip(grads, max_norm: float):
    n = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def opt_update(o: Dict, grads, state, params, step):
    """One inner step of the optimizer split; ``grads`` already clipped."""
    t = jnp.asarray(step, jnp.float32) + 1.0
    b1, b2 = o["adam_betas"]
    lr_m, lr_a = lr_at(o["learning_rate"], step, o), lr_at(o["adam_lr"],
                                                           step, o)
    beta = o["muon_momentum"]

    def upd(path, p, g, mu, m, v):
        g = g.astype(jnp.float32)
        if _uses_adam(path, p):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                        + o["adam_eps"])
            u = -lr_a * (u + o["weight_decay"] * p)
            return p + u.astype(p.dtype), mu, m, v
        mu = beta * mu + g
        ortho = orthogonalize(g + beta * mu, o["muon_ns_steps"])
        scale = math.sqrt(max(1.0, g.shape[-2] / g.shape[-1]))
        return p + (-lr_m * scale * ortho).astype(p.dtype), mu, m, v

    out = jax.tree_util.tree_map_with_path(upd, params, grads, state["mu"],
                                           state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda x: x[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"mu": pick(1), "m": pick(2), "v": pick(3)}


def outer_update(d: Dict, anchor, worker, v):
    """DiLoCo outer step for one worker: Nesterov SGD on the delta."""
    mu, eta = d["outer_momentum"], d["outer_lr"]

    def upd(a, w, vv):
        delta = w.astype(jnp.float32) - a.astype(jnp.float32)
        vv = mu * vv + delta
        step = delta + mu * vv if d["nesterov"] else vv
        return (a + eta * step).astype(a.dtype), vv

    out = jax.tree.map(upd, anchor, worker, v)
    pick = lambda i: jax.tree.map(lambda x: x[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1)


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Per-leaf Frobenius norms keyed by path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def moment_norms(state) -> Dict[str, jax.Array]:
    """Per-leaf norms of the first moment: Muon's momentum for a weight
    matrix, AdamW's ``m`` for the rest."""
    pick = jax.tree.map(lambda mu, m: mu if mu.size else m, state["mu"],
                        state["m"])
    return leaf_norms(pick)


def make_train_step(m: Dict, o: Dict, chunk: int, dtype: str = "float32"):
    """jit(params, state, tokens, labels, step) -> (params, state, loss,
    per-leaf norms of the clipped gradient)."""
    mm = matmul(dtype)

    def step_fn(params, state, tokens, labels, step):
        lf = lambda p: loss(m, p, tokens, labels, chunk, mm)
        val, grads = jax.value_and_grad(lf)(params)
        grads = clip(grads, o["grad_clip"])
        params, state = opt_update(o, grads, state, params, step)
        return params, state, val, leaf_norms(grads)

    return jax.jit(step_fn, donate_argnums=(0, 1))


def precision():
    """``highest``: float32 matmuls in full, for the reference and for the
    fp8 control, whose rounding is explicit."""
    return jax.default_matmul_precision("highest")
