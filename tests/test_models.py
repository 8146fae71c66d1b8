"""Model substrate: forward shapes, no NaNs, decode==teacher-forced forward
for every stateful family, attention path equivalence."""
import jax
import jax.numpy as jnp
import pytest

from helpers import tiny_batch, tiny_cfg
from repro.models import attention as attn_mod
from repro.models.transformer import build_model, init_params

FAMILIES = ["dense", "moe", "ssm", "hybrid", "audio", "vlm"]


@pytest.mark.parametrize("family", FAMILIES)
def test_forward_shapes_and_finite(family):
    cfg = tiny_cfg(family)
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    batch = tiny_batch(cfg)
    logits, aux = jax.jit(m.forward)(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab())
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, metrics = jax.jit(m.loss)(params, batch)
    assert bool(jnp.isfinite(loss))


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid", "vlm"])
def test_decode_matches_forward(family):
    # capacity high enough that no token is dropped — otherwise prefill
    # (capacity per 24 tokens) and decode (capacity per 1 token) legitimately
    # differ, as in any capacity-based MoE system
    cfg = tiny_cfg(family, moe_capacity_factor=8.0)
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(1))
    S = 24
    toks = jax.random.randint(jax.random.key(2), (2, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.num_image_tokens:
        batch["patches"] = 0.1 * jnp.ones((2, cfg.num_image_tokens,
                                           cfg.d_model))
    full, _ = jax.jit(m.forward)(params, batch)
    cache = m.init_cache(2, S + cfg.num_image_tokens)
    step = jax.jit(m.decode_step)
    if cfg.num_image_tokens:
        pytest.skip("vlm decode starts after a prefill with patches; "
                    "covered by serving tests for text-only")
    outs = []
    for t in range(S):
        lg, cache = step(params, cache,
                         {"token": toks[:, t:t + 1], "position": jnp.int32(t)})
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 3e-3


def test_decode_matches_forward_encdec():
    cfg = tiny_cfg("audio")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(1))
    S = 12
    toks = jax.random.randint(jax.random.key(2), (2, S), 0, cfg.vocab_size)
    frames = 0.3 * jax.random.normal(jax.random.key(3),
                                     (2, cfg.encoder_seq_len, cfg.d_model))
    full, _ = jax.jit(m.forward)(params, {"tokens": toks, "frames": frames})

    # build the cross cache from the encoder output, then decode step-wise
    from repro.models.attention import precompute_cross_cache
    from repro.models.transformer import encode
    memory = encode(params, frames, cfg)
    cache = m.init_cache(2, S)
    crosses = [precompute_cross_cache(
        jax.tree.map(lambda x: x[i], params["cross"])["attn"], memory, cfg)
        for i in range(cfg.num_layers)]
    cache["cross"] = {
        "k": jnp.stack([c["k"] for c in crosses]),
        "v": jnp.stack([c["v"] for c in crosses]),
    }
    step = jax.jit(m.decode_step)
    outs = []
    for t in range(S):
        lg, cache = step(params, cache,
                         {"token": toks[:, t:t + 1], "position": jnp.int32(t)})
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 3e-3


def test_sliding_window_ring_buffer_decode():
    cfg = tiny_cfg("dense", window=8)
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(1))
    S = 24
    toks = jax.random.randint(jax.random.key(2), (2, S), 0, cfg.vocab_size)
    full, _ = jax.jit(m.forward)(params, {"tokens": toks})
    cache = m.init_cache(2, 8)     # ring buffer == window
    step = jax.jit(m.decode_step)
    outs = []
    for t in range(S):
        lg, cache = step(params, cache,
                         {"token": toks[:, t:t + 1], "position": jnp.int32(t)})
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(dec - full))) < 3e-3


@pytest.mark.parametrize("impl", ["blocked"])
def test_attention_impl_equivalence(impl):
    cfg = tiny_cfg("dense")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(5), (2, 32), 0, cfg.vocab_size)
    ref, _ = m.forward(params, {"tokens": toks})
    from repro.models.transformer import forward_lm
    out, _ = forward_lm(params, {"tokens": toks}, cfg, impl=impl)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


# Tiny stand-ins for the training cells' attention: nanochat-d20's 128-wide
# heads, and Qwen1.5-0.5B's 64-wide heads with QKV bias.
FLASH_CFGS = {
    "d20-like": dict(d_model=256, num_heads=2, num_kv_heads=2, head_dim=128),
    "qwen-like": dict(d_model=128, num_heads=2, num_kv_heads=2, head_dim=64,
                      qkv_bias=True),
}


@pytest.mark.parametrize("window", [None, 96])
@pytest.mark.parametrize("kind", sorted(FLASH_CFGS))
def test_flash_attention_matches_direct(kind, window):
    """attention(impl="flash") against impl="direct": values and gradients
    with respect to the attention parameters, through jax.checkpoint and a
    jax.vmap over workers, as core/diloco.py wraps the step.  The flash
    path rounds every matmul operand to bfloat16 (the direct path on a CPU
    does not), so both sides agree to 4u of the largest entry, u = 2^-8
    (see test_kernels.py)."""
    from repro.models.layers import split_logical
    cfg = tiny_cfg("dense", **FLASH_CFGS[kind])
    S, workers = 256, 2
    p, _ = split_logical(attn_mod.init_attention(jax.random.key(0), cfg))
    # move the biases off their zero init so their gradients carry weight
    p = {n: a + 0.1 * jax.random.normal(jax.random.key(i), a.shape)
         for i, (n, a) in enumerate(sorted(p.items()))}
    x = jax.random.normal(jax.random.key(1), (workers, 1, S, cfg.d_model))
    y = jax.random.normal(jax.random.key(2), (1, S, cfg.d_model))
    positions = jnp.arange(S)

    def run(impl):
        def loss(p, x):
            out = attn_mod.attention(p, x, cfg, positions=positions,
                                     window=window, impl=impl)
            return jnp.sum(out * y)
        step = jax.value_and_grad(jax.checkpoint(loss))
        return jax.jit(jax.vmap(step, in_axes=(None, 0)))(p, x)

    (val, grads), (ref_val, ref_grads) = run("flash"), run("direct")
    scale = float(jnp.max(jnp.abs(ref_val)))
    assert float(jnp.max(jnp.abs(val - ref_val))) < 4 * 2.0 ** -8 * scale
    assert sorted(grads) == sorted(p)
    for name in grads:
        g, r = grads[name], ref_grads[name]
        assert g.shape == (workers,) + p[name].shape
        rel = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
        assert rel < 4 * 2.0 ** -8, (name, rel)


def _select_before_flash(*, S, Sk, causal, static_window, window):
    """The path choice as it stood before the flash kernel took TPU calls."""
    if (static_window and window and window < Sk
            and Sk > attn_mod.DIRECT_MAX_KV and causal and S == Sk
            and S % min(attn_mod.BLOCK_Q, S) == 0):
        return "banded"
    if Sk > attn_mod.DIRECT_MAX_KV:
        return "blocked"
    return "direct"


SELECT_CASES = [
    # (S, Sk, causal, static_window, window, fp8)
    (2048, 2048, True, True, None, False),   # the training cells
    (2048, 2048, True, True, 512, False),    # static sliding window
    (8192, 8192, True, True, 1024, False),   # long, windowed (banded on CPU)
    (8192, 8192, True, True, None, False),   # long, global (blocked on CPU)
    (2048, 2048, True, False, None, False),  # traced per-layer window
    (2048, 1024, False, True, None, False),  # cross-attention
    (200, 200, True, True, None, False),     # length off the block
    (2048, 2048, True, True, None, True),    # fp8 matmuls
]


@pytest.mark.parametrize("case", SELECT_CASES)
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_select_impl_flash_only_on_tpu(backend, case):
    """The choice is a pure function of backend, shapes and window: flash
    for causal self-attention with a static window and a block-multiple
    length on a TPU, and on a CPU exactly the choice made before."""
    S, Sk, causal, static_window, window, fp8 = case
    got = attn_mod.select_impl(backend=backend, S=S, Sk=Sk, causal=causal,
                               static_window=static_window, window=window,
                               fp8=fp8)
    before = _select_before_flash(S=S, Sk=Sk, causal=causal,
                                  static_window=static_window, window=window)
    flash_ok = causal and S == Sk and static_window and not fp8 \
        and S % 128 == 0
    assert got == ("flash" if backend == "tpu" and flash_ok else before)


def test_banded_swa_equals_direct():
    """The O(S·W) banded prefill must match the O(S²) masked path."""
    cfg = tiny_cfg("dense", window=8)
    build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(6), (2, 32), 0, cfg.vocab_size)
    from repro.models.transformer import forward_lm
    ref, _ = forward_lm(params, {"tokens": toks}, cfg, impl="direct")
    old_bq = attn_mod.BLOCK_Q
    attn_mod.BLOCK_Q = 16
    try:
        out, _ = forward_lm(params, {"tokens": toks}, cfg, impl="banded")
    finally:
        attn_mod.BLOCK_Q = old_bq
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-3


def test_per_layer_window_pattern():
    """Layers with different windows really see different contexts."""
    cfg_all_global = tiny_cfg("dense")
    cfg_windowed = tiny_cfg("dense", window_pattern=(4, 4))
    pa, _ = init_params(cfg_all_global, jax.random.key(0))
    ma = build_model(cfg_all_global)
    mw = build_model(cfg_windowed)
    toks = jax.random.randint(jax.random.key(7), (1, 32), 0, 97)
    la, _ = ma.forward(pa, {"tokens": toks})
    lw, _ = mw.forward(pa, {"tokens": toks})
    assert float(jnp.max(jnp.abs(la - lw))) > 1e-4  # must differ


@pytest.mark.slow
def test_chunked_ce_matches_full():
    """cfg.loss_chunk must not change the loss value or its gradients."""
    from repro.models.transformer import lm_loss
    cfg = tiny_cfg("dense")
    build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 30), 0, cfg.vocab_size)
    batch = {"tokens": toks,
             "labels": ((toks + 1) % cfg.vocab_size).at[:, :3].set(-1)}
    l0, _ = lm_loss(params, batch, cfg)
    l1, _ = lm_loss(params, batch, cfg.with_(loss_chunk=8))
    assert abs(float(l0) - float(l1)) < 1e-5
    g0 = jax.grad(lambda p: lm_loss(p, batch, cfg)[0])(params)
    g1 = jax.grad(lambda p: lm_loss(p, batch, cfg.with_(loss_chunk=8))[0])(params)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
    assert err < 1e-5
