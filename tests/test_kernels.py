"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (flash_attention,
                                           reference_attention,
                                           reference_attention_fp8)
from repro.kernels.rmsnorm import (reference_rmsnorm,
                                   reference_rmsnorm_residual, rmsnorm,
                                   rmsnorm_residual)
from repro.kernels.ssd import reference_ssd, ssd


@pytest.mark.parametrize("B,H,KV,S,D", [
    (1, 4, 2, 256, 64),
    (2, 2, 2, 128, 32),
    (1, 8, 4, 256, 128),
    (1, 2, 1, 384, 64),
    (1, 1, 1, 128, 128),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_sweep(B, H, KV, S, D, causal, window):
    ks = jax.random.split(jax.random.key(B * S + H + D), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          dot_dtype=jnp.float32)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
    out = flash_attention(q, k, v, dot_dtype=jnp.float32)
    ref = reference_attention(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert out.dtype == dtype
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None)])
def test_flash_attention_fp8_matches_oracle(causal, window):
    """``fp8=True`` runs QK^T on per-row e4m3 tiles; the oracle pushes the
    same rows through quantize-dequantize and runs the exact math.  The
    fp8 result must match ITS oracle tightly while differing measurably
    from the exact attention (proof the narrow path is live)."""
    ks = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, fp8=True,
                          dot_dtype=jnp.float32)
    ref = reference_attention_fp8(q, k, v, causal=causal, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    exact = reference_attention(q, k, v, causal=causal, window=window)
    assert float(jnp.max(jnp.abs(ref - exact))) > 1e-3


def test_flash_attention_mismatched_qk_len():
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention(q, k, v, causal=False, dot_dtype=jnp.float32)
    ref = reference_attention(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


# bfloat16 keeps 8 significant bits: each rounded operand is off by at
# most u = 2^-8 relative.  A product of two rounded operands is off by 2u,
# and the backward chains two rounded matmuls (dP -> dS -> dQ/dK), so 4u of
# the largest entry bounds every output.  The lower bound (u/8) shows the
# tiles really were rounded: float32 operands land near 1e-7.
BF16_U = 2.0 ** -8


@pytest.mark.parametrize("dot_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,KV,D,window", [
    (4, 2, 64, None),        # GQA, two query heads per KV head
    (2, 2, 128, None),
    (4, 1, 64, 64),          # sliding window, four heads per KV head
    (2, 2, 128, 96),
])
def test_flash_attention_grad_matches_reference(H, KV, D, window, dot_dtype):
    """O and jax.grad (dQ, dK, dV) of the kernel's custom_vjp against
    jax.grad of the materialised-scores oracle: causal, S 256, blocks of
    128 (so the diagonal, skipped and, with a window, partly visible
    blocks all occur)."""
    S = 256
    ks = jax.random.split(jax.random.key(S + H + D), 4)
    q = jax.random.normal(ks[0], (1, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, KV, S, D), jnp.float32)
    do = jax.random.normal(ks[3], (1, H, S, D), jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, window=window, **kw) * do)

    out = flash_attention(q, k, v, window=window, bq=128, bk=128,
                          dot_dtype=dot_dtype)
    grads = jax.grad(loss(flash_attention, bq=128, bk=128,
                          dot_dtype=dot_dtype), argnums=(0, 1, 2))(q, k, v)
    ref = reference_attention(q, k, v, window=window)
    ref_grads = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (out, *grads),
                          (ref, *ref_grads)):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        if dot_dtype == "float32":
            assert rel < 1e-5, (name, rel)
        else:
            assert BF16_U / 8 < rel < 4 * BF16_U, (name, rel)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 64, 4, 16, 32, 16),
    (1, 100, 2, 8, 16, 32),     # non-multiple S -> padding path
    (1, 128, 8, 32, 64, 128),   # single chunk
    (2, 96, 1, 64, 8, 16),
])
def test_ssd_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(jax.random.key(S + N), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = jnp.ones((H,))
    y, h = ssd(x, dt, A, Bm, Cm, D, chunk=chunk)
    yr, hr = reference_ssd(x, dt, A, Bm, Cm, D, chunk=chunk)
    assert float(jnp.max(jnp.abs(y - yr))) < 1e-3
    assert float(jnp.max(jnp.abs(h - hr))) < 1e-3


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == naive per-token recurrence (the gold-standard oracle)."""
    B, S, H, P, N = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = 0.5 * jnp.ones((H,))
    y, hT = ssd(x, dt, A, Bm, Cm, D, chunk=8)

    from repro.models.ssm import ssd_decode_step
    h = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(S):
        yt, h = ssd_decode_step(h, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(yt)
    y_seq = jnp.stack(ys, axis=1)
    assert float(jnp.max(jnp.abs(y - y_seq))) < 1e-3
    assert float(jnp.max(jnp.abs(hT - h))) < 1e-3


@pytest.mark.parametrize("R,d", [(40, 96), (256, 64), (7, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(R, d, dtype):
    x = jax.random.normal(jax.random.key(R), (R, d)).astype(dtype)
    s = jax.random.normal(jax.random.key(d), (d,))
    out = rmsnorm(x, s)
    ref = reference_rmsnorm(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


def test_rmsnorm_residual():
    x = jax.random.normal(jax.random.key(0), (3, 40, 96))
    r = jax.random.normal(jax.random.key(1), (3, 40, 96))
    s = jnp.ones((96,))
    o, res = rmsnorm_residual(x, r, s)
    orf, resr = reference_rmsnorm_residual(x, r, s)
    assert float(jnp.max(jnp.abs(o - orf))) < 1e-5
    assert float(jnp.max(jnp.abs(res - resr))) < 1e-5


def test_ssd_kernel_in_model_path():
    """cfg.use_pallas=True must produce identical logits to the jnp path."""
    from helpers import tiny_cfg
    from repro.models.transformer import build_model, forward_lm, init_params
    cfg = tiny_cfg("ssm", ssm_chunk=16)
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    ref, _ = m.forward(params, {"tokens": toks})
    out, _ = forward_lm(params, {"tokens": toks}, cfg.with_(use_pallas=True))
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3


@pytest.mark.parametrize("B,KV,G,S,D,window", [
    (2, 2, 2, 256, 64, 0),
    (1, 4, 1, 512, 128, 0),
    (2, 1, 4, 256, 64, 64),     # sliding window
    (1, 2, 3, 256, 32, 0),      # odd group size
])
def test_decode_attention_kernel(B, KV, G, S, D, window):
    from repro.kernels.decode_attention import (decode_attention,
                                                reference_decode_attention)
    ks = jax.random.split(jax.random.key(B * S + D), 4)
    q = jax.random.normal(ks[0], (B, KV, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    # ring-buffer-ish positions: first 3/4 filled with a WRAPPED layout,
    # last 1/4 empty (-1)
    fill = 3 * S // 4
    base = jax.random.randint(ks[3], (B, 1), fill, fill + 100)
    pos = (base - 1 - jnp.arange(S)[None, :]) % (base + 1)
    pos = jnp.where(jnp.arange(S)[None, :] < fill, pos, -1).astype(jnp.int32)
    q_pos = base[:, 0].astype(jnp.int32)
    out = decode_attention(q, k, v, pos, q_pos, window=window, bk=128)
    ref = reference_decode_attention(q, k, v, pos, q_pos, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_decode_attention_ignores_empty_slots():
    from repro.kernels.decode_attention import (decode_attention,
                                                reference_decode_attention)
    ks = jax.random.split(jax.random.key(5), 3)
    B, KV, G, S, D = 1, 1, 2, 128, 32
    q = jax.random.normal(ks[0], (B, KV, G, D))
    k = jax.random.normal(ks[1], (B, KV, S, D))
    v = jax.random.normal(ks[2], (B, KV, S, D))
    pos_full = jnp.arange(S, dtype=jnp.int32)[None]
    # poisoning slots beyond q_pos must not change the output
    q_pos = jnp.asarray([63], jnp.int32)
    out1 = decode_attention(q, k, v, pos_full, q_pos, bk=64)
    k2 = k.at[:, :, 100:].set(1e4)
    v2 = v.at[:, :, 100:].set(-1e4)
    out2 = decode_attention(q, k2, v2, pos_full, q_pos, bk=64)
    assert float(jnp.max(jnp.abs(out1 - out2))) == 0.0


@pytest.mark.parametrize("S,KV,G,NB,bs,MB,D,window", [
    (3, 2, 2, 8, 16, 3, 32, 0),
    (2, 1, 4, 6, 8, 4, 64, 0),
    (4, 2, 1, 8, 16, 2, 32, 12),    # sliding window
])
def test_paged_decode_attention_kernel(S, KV, G, NB, bs, MB, D, window):
    """Block-table-indexed kernel vs the paged jnp oracle, and the paged
    oracle vs the contiguous oracle on the gathered layout."""
    from repro.kernels.decode_attention import (
        paged_decode_attention, reference_decode_attention,
        reference_paged_decode_attention)
    ks = jax.random.split(jax.random.key(S * NB + D), 4)
    q = jax.random.normal(ks[0], (S, KV, G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, KV, D), jnp.float32)
    # each slot owns a random prefix of mapped (shuffled) physical blocks
    rng = np.random.default_rng(S + NB)
    tables = np.full((S, MB), -1, np.int32)
    perm = rng.permutation(NB)
    q_pos = np.zeros((S,), np.int32)
    off = 0
    for s in range(S):
        n = int(rng.integers(1, MB + 1))
        tables[s, :n] = perm[off:off + n]
        off += n
        q_pos[s] = int(rng.integers((n - 1) * bs, n * bs))
    tables, q_pos = jnp.asarray(tables), jnp.asarray(q_pos)
    out = paged_decode_attention(q, kp, vp, tables, q_pos, window=window)
    ref = reference_paged_decode_attention(q, kp, vp, tables, q_pos,
                                           window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    # cross-check against the contiguous oracle on the gathered layout
    kc = kp[jnp.maximum(tables, 0)].reshape(S, MB * bs, KV, D)
    vc = vp[jnp.maximum(tables, 0)].reshape(S, MB * bs, KV, D)
    pos = jnp.where(jnp.repeat(tables >= 0, bs, axis=1),
                    jnp.arange(MB * bs)[None], -1)
    ref2 = reference_decode_attention(q, kc.transpose(0, 2, 1, 3),
                                      vc.transpose(0, 2, 1, 3), pos, q_pos,
                                      window=window)
    assert float(jnp.max(jnp.abs(ref - ref2))) < 2e-5


@pytest.mark.parametrize("S,T,KV,G,NB,bs,MB,D,window", [
    (3, 4, 2, 2, 8, 16, 3, 32, 0),
    (2, 6, 1, 4, 6, 8, 4, 64, 0),
    (4, 3, 2, 1, 8, 16, 2, 32, 12),   # sliding window
])
def test_paged_verify_attention_kernel(S, T, KV, G, NB, bs, MB, D, window):
    """Multi-query-per-slot (speculative verification) kernel vs the jnp
    oracle, with ragged per-slot query counts, padding rows and an
    inactive slot."""
    from repro.kernels.decode_attention import (
        paged_verify_attention, reference_paged_verify_attention)
    ks = jax.random.split(jax.random.key(S * NB + T), 4)
    q = jax.random.normal(ks[0], (S, T, KV, G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, KV, D), jnp.float32)
    rng = np.random.default_rng(S + NB + T)
    tables = np.full((S, MB), -1, np.int32)
    perm = rng.permutation(NB)
    start = np.zeros((S,), np.int32)
    n_tok = np.zeros((S,), np.int32)
    off = 0
    for s in range(S):
        n = int(rng.integers(1, MB + 1))
        tables[s, :n] = perm[off:off + n]
        off += n
        n_tok[s] = int(rng.integers(1, T + 1))   # ragged live counts
        start[s] = int(rng.integers(0, n * bs - int(n_tok[s]) + 1))
    start[-1], n_tok[-1] = -1, 0                 # one inactive slot
    tables = jnp.asarray(tables)
    start, n_tok = jnp.asarray(start), jnp.asarray(n_tok)
    out = paged_verify_attention(q, kp, vp, tables, start, n_tok,
                                 window=window)
    ref = reference_paged_verify_attention(q, kp, vp, tables, start, n_tok,
                                           window=window)
    # compare live rows only (padding rows are documented garbage)
    for s in range(S):
        n = int(n_tok[s]) if int(start[s]) >= 0 else 0
        if n:
            d = jnp.max(jnp.abs(out[s, :n] - ref[s, :n]))
            assert float(d) < 2e-5, (s, float(d))


def test_paged_verify_attention_t1_matches_single_query_kernel():
    """T=1 degenerates to the single-query paged kernel exactly."""
    from repro.kernels.decode_attention import (paged_decode_attention,
                                                paged_verify_attention)
    ks = jax.random.split(jax.random.key(3), 3)
    S, KV, G, NB, bs, MB, D = 3, 2, 2, 6, 8, 3, 32
    q = jax.random.normal(ks[0], (S, 1, KV, G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, KV, D), jnp.float32)
    tables = jnp.asarray([[0, 1, -1], [2, -1, -1], [3, 4, 5]], jnp.int32)
    q_pos = jnp.asarray([9, 4, 20], jnp.int32)
    a = paged_verify_attention(q, kp, vp, tables, q_pos,
                               jnp.ones((S,), jnp.int32))
    b = paged_decode_attention(q[:, 0], kp, vp, tables, q_pos)
    assert float(jnp.max(jnp.abs(a[:, 0] - b))) < 2e-5


def test_paged_verify_attention_causal_among_fresh_tokens():
    """Query token t must see tokens 0..t of the same round (positional
    causality) and never later ones: poisoning the pool at positions
    beyond each query's own position leaves its row unchanged."""
    from repro.kernels.decode_attention import paged_verify_attention
    ks = jax.random.split(jax.random.key(5), 3)
    S, T, KV, G, NB, bs, MB, D = 1, 4, 1, 2, 4, 8, 2, 32
    q = jax.random.normal(ks[0], (S, T, KV, G, D))
    kp = jax.random.normal(ks[1], (NB, bs, KV, D))
    vp = jax.random.normal(ks[2], (NB, bs, KV, D))
    tables = jnp.asarray([[1, 3]], jnp.int32)
    start = jnp.asarray([5], jnp.int32)          # queries at 5,6,7,8
    n_tok = jnp.asarray([T], jnp.int32)
    out1 = paged_verify_attention(q, kp, vp, tables, start, n_tok)
    # poison position 8 (block 3, offset 0) — only query t=3 may see it
    kp2 = kp.at[3, 0].set(1e4)
    vp2 = vp.at[3, 0].set(-1e4)
    out2 = paged_verify_attention(q, kp2, vp2, tables, start, n_tok)
    assert float(jnp.max(jnp.abs(out1[0, :3] - out2[0, :3]))) == 0.0
    assert float(jnp.max(jnp.abs(out1[0, 3] - out2[0, 3]))) > 1.0


def _paged_case(S, NB, bs, MB, KV, D, seed, T=0):
    """Random pools + shuffled block tables shared by the fp8/dequant
    paged-kernel tests (same construction as the plain sweeps)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    qshape = (S, T, KV, 2, D) if T else (S, KV, 2, D)
    q = jax.random.normal(ks[0], qshape, jnp.float32)
    kp = jax.random.normal(ks[1], (NB, bs, KV, D), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, bs, KV, D), jnp.float32)
    rng = np.random.default_rng(seed)
    tables = np.full((S, MB), -1, np.int32)
    perm = rng.permutation(NB)
    pos = np.zeros((S,), np.int32)
    n_tok = np.zeros((S,), np.int32)
    off = 0
    for s in range(S):
        n = int(rng.integers(1, MB + 1))
        tables[s, :n] = perm[off:off + n]
        off += n
        if T:
            n_tok[s] = int(rng.integers(1, T + 1))
            pos[s] = int(rng.integers(0, n * bs - int(n_tok[s]) + 1))
        else:
            pos[s] = int(rng.integers((n - 1) * bs, n * bs))
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(n_tok))


@pytest.mark.parametrize("window", [0, 12])
def test_paged_decode_attention_fp8_matches_oracle(window):
    from repro.kernels.decode_attention import (
        paged_decode_attention, reference_paged_decode_attention,
        reference_paged_decode_attention_fp8)
    q, kp, vp, tables, q_pos, _ = _paged_case(3, 8, 16, 3, 2, 32, seed=11)
    out = paged_decode_attention(q, kp, vp, tables, q_pos, window=window,
                                 fp8=True)
    ref = reference_paged_decode_attention_fp8(q, kp, vp, tables, q_pos,
                                               window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-6
    exact = reference_paged_decode_attention(q, kp, vp, tables, q_pos,
                                             window=window)
    assert float(jnp.max(jnp.abs(ref - exact))) > 1e-3


@pytest.mark.parametrize("window", [0, 12])
def test_paged_verify_attention_fp8_matches_oracle(window):
    from repro.kernels.decode_attention import (
        paged_verify_attention, reference_paged_verify_attention_fp8)
    q, kp, vp, tables, start, n_tok = _paged_case(3, 8, 16, 3, 2, 32,
                                                  seed=13, T=4)
    out = paged_verify_attention(q, kp, vp, tables, start, n_tok,
                                 window=window, fp8=True)
    ref = reference_paged_verify_attention_fp8(q, kp, vp, tables, start,
                                               n_tok, window=window)
    for s in range(q.shape[0]):
        n = int(n_tok[s]) if int(start[s]) >= 0 else 0
        if n:
            d = jnp.max(jnp.abs(out[s, :n] - ref[s, :n]))
            assert float(d) < 5e-6, (s, float(d))


def _quantized_pool(kp, vp, dtype):
    """Quantize-on-scatter view of a full-precision pool: narrow payload
    plus (NB, bs, KV) per-token-per-head scales — the exact layout
    ``init_paged_cache`` stores."""
    from repro.kernels.quantize import reference_quantize_axis
    kq, ks = reference_quantize_axis(kp, axis=-1, dtype=dtype)
    vq, vs = reference_quantize_axis(vp, axis=-1, dtype=dtype)
    return kq, vq, ks[..., 0], vs[..., 0]


@pytest.mark.parametrize("dtype", ["int8", "fp8_e4m3", "fp8_e5m2"])
@pytest.mark.parametrize("window", [0, 12])
def test_paged_decode_attention_dequant_matches_oracle(dtype, window):
    """Dequant-on-load kernel vs the materialize-then-attend oracle on all
    three pool dtypes, and the quantized result genuinely differs from the
    full-precision pool's (the narrow payload is what's being read)."""
    from repro.kernels.decode_attention import (
        paged_decode_attention_dequant, reference_paged_decode_attention,
        reference_paged_decode_attention_dequant)
    q, kp, vp, tables, q_pos, _ = _paged_case(3, 8, 16, 3, 2, 32, seed=17)
    kq, vq, ks, vs = _quantized_pool(kp, vp, dtype)
    out = paged_decode_attention_dequant(q, kq, vq, ks, vs, tables, q_pos,
                                         window=window)
    ref = reference_paged_decode_attention_dequant(
        q, kq, vq, ks, vs, tables, q_pos, window=window)
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-6
    exact = reference_paged_decode_attention(q, kp, vp, tables, q_pos,
                                             window=window)
    assert float(jnp.max(jnp.abs(ref - exact))) > 1e-4


@pytest.mark.parametrize("dtype", ["int8", "fp8_e4m3"])
def test_paged_verify_attention_dequant_matches_oracle(dtype):
    from repro.kernels.decode_attention import (
        paged_verify_attention_dequant,
        reference_paged_verify_attention_dequant)
    q, kp, vp, tables, start, n_tok = _paged_case(3, 8, 16, 3, 2, 32,
                                                  seed=19, T=4)
    kq, vq, ks, vs = _quantized_pool(kp, vp, dtype)
    out = paged_verify_attention_dequant(q, kq, vq, ks, vs, tables, start,
                                         n_tok)
    ref = reference_paged_verify_attention_dequant(
        q, kq, vq, ks, vs, tables, start, n_tok)
    for s in range(q.shape[0]):
        n = int(n_tok[s]) if int(start[s]) >= 0 else 0
        if n:
            d = jnp.max(jnp.abs(out[s, :n] - ref[s, :n]))
            assert float(d) < 5e-6, (s, float(d))


def test_paged_decode_attention_ignores_unmapped_and_stale():
    """Poisoning unmapped blocks and positions beyond q_pos must not change
    the output."""
    from repro.kernels.decode_attention import paged_decode_attention
    ks = jax.random.split(jax.random.key(9), 3)
    S, KV, G, NB, bs, MB, D = 1, 1, 2, 4, 16, 3, 32
    q = jax.random.normal(ks[0], (S, KV, G, D))
    kp = jax.random.normal(ks[1], (NB, bs, KV, D))
    vp = jax.random.normal(ks[2], (NB, bs, KV, D))
    tables = jnp.asarray([[2, 0, -1]], jnp.int32)
    q_pos = jnp.asarray([20], jnp.int32)          # valid: block 2 + 5 of blk 0
    out1 = paged_decode_attention(q, kp, vp, tables, q_pos)
    kp2 = kp.at[1].set(1e4).at[3].set(1e4)        # unmapped blocks
    vp2 = vp.at[1].set(-1e4).at[3].set(-1e4)
    kp2 = kp2.at[0, 5:].set(1e4)                  # stale: beyond q_pos
    vp2 = vp2.at[0, 5:].set(-1e4)
    out2 = paged_decode_attention(q, kp2, vp2, tables, q_pos)
    assert float(jnp.max(jnp.abs(out1 - out2))) == 0.0
