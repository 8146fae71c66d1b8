"""Optimizers: AdamW reference math, Muon orthogonalization, partitioning,
schedules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import OptimizerConfig
from repro.optim import (adamw, apply_updates, lr_schedule, muon,
                         nanochat_optimizer, newton_schulz, sgd_nesterov)
from repro.optim.combined import partition_label


def test_adamw_matches_numpy_reference():
    opt = adamw(lr=0.1, betas=(0.9, 0.99), eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.25])}
    st = opt.init(p)
    upd, st = opt.update(g, st, p, 0)
    m = 0.1 * np.array([0.5, 0.25])
    v = 0.01 * np.array([0.25, 0.0625])
    mhat, vhat = m / 0.1, v / 0.01
    expect = -0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(upd["w"]), expect, rtol=1e-5)


def test_muon_orthogonalizes():
    G = jax.random.normal(jax.random.key(0), (24, 16))
    O = newton_schulz(G, steps=5)
    sv = jnp.linalg.svd(O, compute_uv=False)
    assert float(sv.min()) > 0.5 and float(sv.max()) < 1.5


def test_muon_stacked_params():
    """Muon must orthogonalize each layer of a (L, m, n) stack independently."""
    G = jax.random.normal(jax.random.key(0), (4, 24, 16))
    O = newton_schulz(G)
    single = newton_schulz(G[2])
    np.testing.assert_allclose(np.asarray(O[2]), np.asarray(single),
                               rtol=1e-4, atol=1e-5)


def _newton_schulz_transposing(G, steps=5, eps=1e-7):
    """Straight-line float32 transcription of the transposing form: a tall
    matrix is swapped wide, normalised, iterated with A = X·Xᵀ and
    X <- aX + (bA + cA²)·X, and swapped back."""
    a, b, c = 3.4445, -4.7750, 2.0315
    X = G.astype(jnp.float32)
    tall = X.shape[-2] > X.shape[-1]
    if tall:
        X = jnp.swapaxes(X, -1, -2)
    X = X / (jnp.sqrt(jnp.sum(X * X, axis=(-2, -1), keepdims=True)) + eps)
    for _ in range(steps):
        A = X @ jnp.swapaxes(X, -1, -2)
        X = a * X + (b * A + c * (A @ A)) @ X
    return jnp.swapaxes(X, -1, -2) if tall else X


@pytest.mark.parametrize("shape", [(16, 40), (40, 16), (32, 32), (17, 16),
                                   (3, 24, 16), (2, 3, 16, 24)])
def test_newton_schulz_matches_transposing_form(shape):
    """The small-Gram, transpose-free iteration is the transposing one up to
    float32 accumulation order, and commutes with a transpose."""
    G = jax.random.normal(jax.random.key(len(shape) * 100 + shape[-1]), shape)
    O = np.asarray(newton_schulz(G))
    np.testing.assert_allclose(O, np.asarray(_newton_schulz_transposing(G)),
                               rtol=0, atol=1e-5)
    Ot = np.asarray(newton_schulz(jnp.swapaxes(G, -1, -2)))
    np.testing.assert_allclose(Ot, np.swapaxes(O, -1, -2), rtol=0, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 3, 24, 40), (2, 3, 40, 24)])
def test_newton_schulz_is_straight_line_matmuls(shape):
    """Each iteration is three matmuls on the small Gram side: no loop, and
    no transpose of the iterate."""
    steps = 5
    jaxpr = jax.make_jaxpr(lambda g: newton_schulz(g, steps))(
        jnp.zeros(shape, jnp.float32))
    prims = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert not {"transpose", "scan", "while"} & set(prims)
    assert prims.count("dot_general") == 3 * steps
    small = min(shape[-2:])
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            assert eqn.outvars[0].aval.shape[-2:] in {(small, small),
                                                     shape[-2:]}


def test_sgd_nesterov_math():
    opt = sgd_nesterov(lr=1.0, momentum=0.5, nesterov=True)
    p = {"w": jnp.zeros(2)}
    g = {"w": jnp.asarray([1.0, 2.0])}
    st = opt.init(p)
    upd, st = opt.update(g, st, p, 0)
    v = np.array([1.0, 2.0])
    expect = -(np.array([1.0, 2.0]) + 0.5 * v)
    np.testing.assert_allclose(np.asarray(upd["w"]), expect)


def test_partition_label_routing():
    from repro.models.transformer import init_params
    from helpers import tiny_cfg
    cfg = tiny_cfg("hybrid")
    params, _ = init_params(cfg, jax.random.key(0))
    labels = jax.tree_util.tree_map_with_path(partition_label, params)
    flat = jax.tree_util.tree_flatten_with_path(labels)[0]
    by = {"muon": [], "adamw": []}
    for path, lab in flat:
        by[lab].append("/".join(str(getattr(p, "key", p)) for p in path))
    assert any("wq" in p for p in by["muon"])
    assert any("table" in p for p in by["adamw"])
    assert any("A_log" in p for p in by["adamw"])
    assert any("conv_w" in p for p in by["adamw"])
    assert not any("wq" in p for p in by["adamw"])


def test_partitioned_state_is_lean():
    """Per-label optimizer state must not allocate for leaves it doesn't own."""
    from repro.models.transformer import init_params
    from helpers import tiny_cfg
    cfg = tiny_cfg("dense")
    params, _ = init_params(cfg, jax.random.key(0))
    opt = nanochat_optimizer(OptimizerConfig())
    st = opt.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_state = sum(x.size for x in jax.tree.leaves(st))
    # muon: 1x matrices; adamw: 2x the rest -> strictly less than 2x params
    assert n_state < 2 * n_params


def test_schedules():
    f = lr_schedule("wsd", 1.0, 100, warmup_steps=10)
    assert float(f(0)) < 0.2
    assert abs(float(f(50)) - 1.0) < 1e-6
    assert float(f(99)) < 0.3
    g = lr_schedule("cosine", 1.0, 100, warmup_steps=0)
    assert float(g(0)) > 0.99
    assert float(g(99)) < 0.05


def test_training_decreases_loss():
    from helpers import tiny_batch, tiny_cfg
    from repro.models.transformer import build_model, init_params
    cfg = tiny_cfg("dense")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    opt = nanochat_optimizer(OptimizerConfig(
        total_steps=60, warmup_steps=5, schedule="constant",
        learning_rate=0.05, adam_lr=2e-3))
    st = opt.init(params)

    @jax.jit
    def step(params, st, batch, i):
        (loss, _), grads = jax.value_and_grad(m.loss, has_aux=True)(params,
                                                                    batch)
        upd, st = opt.update(grads, st, params, i)
        return apply_updates(params, upd), st, loss

    losses = []
    for i in range(50):
        batch = tiny_batch(cfg, B=8, S=32, key=i)
        params, st, loss = step(params, st, batch, jnp.int32(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5
