"""Codec-aware outer-sync transport: codecs, Pallas quant kernels,
pipelined strategy, heterogeneous comm simulator, calibration."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_cfg
from repro.configs.base import DiLoCoConfig, OptimizerConfig
from repro.core import (DiLoCoSync, DistTrainer, PipelinedSync,
                        StreamingSync, make_strategy)
from repro.core.sync import SyncEvent
from repro.core.transport import (BF16Cast, F32Passthrough, Fp8Codec,
                                  Int8Symmetric, make_codec)
from repro.kernels.quantize import (dequantize, quantize_ef,
                                    reference_dequantize,
                                    reference_quantize_ef)
from repro.launch.comm_sim import (CommCalibration, CommModel,
                                   load_calibration, modeled_step_time,
                                   simulate_heterogeneous, simulate_schedule)
from repro.models.transformer import build_model, init_params

OPT = OptimizerConfig(total_steps=100, warmup_steps=0, schedule="constant",
                      learning_rate=0.02, adam_lr=1e-3)


# ---------------------------------------------------------------------------
# Codec round-trips
# ---------------------------------------------------------------------------

def _tree(seed=0, scale=0.01):
    ks = jax.random.split(jax.random.key(seed), 3)
    return {"w": jax.random.normal(ks[0], (3, 8, 5)) * scale,
            "b": jax.random.normal(ks[1], (3, 7)) * scale,
            "s": jax.random.normal(ks[2], (3,)) * scale}


def test_f32_codec_is_identity():
    delta = _tree()
    codec = F32Passthrough()
    payload, res = codec.encode(delta)
    assert res is None and payload.codec == "f32" and payload.scales is None
    back = codec.decode(payload)
    for a, b in zip(jax.tree.leaves(delta), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_codec_exact_on_representable_values():
    """bf16 has an 8-bit mantissa: values already representable in bf16
    round-trip exactly; everything else within relative 2^-8."""
    exact = {"w": jnp.asarray([[1.0, -0.5, 0.375, 2.0 ** -20, 0.0]])}
    codec = BF16Cast()
    back = codec.decode(codec.encode(exact)[0])
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(exact["w"]))
    fuzzy = _tree(seed=3)
    back = codec.decode(codec.encode(fuzzy)[0])
    for a, b in zip(jax.tree.leaves(fuzzy), jax.tree.leaves(back)):
        rel = np.abs(np.asarray(a) - np.asarray(b))
        assert (rel <= np.abs(np.asarray(a)) * 2.0 ** -8 + 1e-12).all()


@pytest.mark.parametrize("use_kernel", [True, False])
def test_int8_codec_error_bound(use_kernel):
    """|dec(enc(x)) - x| <= scale/2 = amax/254 per worker row."""
    delta = _tree(seed=4, scale=0.1)
    codec = Int8Symmetric(use_kernel=use_kernel)
    payload, _ = codec.encode(delta)
    assert payload.codec == "int8" and payload.scales is not None
    back = codec.decode(payload)
    for key in delta:
        x = np.asarray(delta[key]).reshape(3, -1)
        b = np.asarray(back[key]).reshape(3, -1)
        for i in range(3):
            amax = np.abs(x[i]).max()
            assert np.abs(b[i] - x[i]).max() <= amax / 254 + 1e-9


def test_int8_error_feedback_residual_is_the_roundtrip_error():
    delta = _tree(seed=5)
    residual = jax.tree.map(jnp.zeros_like, delta)
    codec = Int8Symmetric()
    payload, new_res = codec.encode(delta, residual)
    back = codec.decode(payload)
    for key in delta:
        np.testing.assert_allclose(
            np.asarray(new_res[key]),
            np.asarray(delta[key]) - np.asarray(back[key]), atol=1e-6)


def test_error_feedback_recovers_accumulated_truncation():
    """A delta far below one quantization step is truncated to zero every
    round WITHOUT error feedback, but accumulates in the residual and
    eventually crosses the wire WITH it."""
    big, tiny = 1.0, 1e-3   # scale = 1/127, tiny << scale/2
    delta = {"w": jnp.asarray([[big, tiny]])}
    codec = Int8Symmetric()
    # no EF: tiny never ships
    shipped = codec.decode(codec.encode(delta)[0])
    assert float(shipped["w"][0, 1]) == 0.0
    # EF: after enough rounds the carried residual ships
    residual = {"w": jnp.zeros((1, 2))}
    total = np.zeros(2)
    for _ in range(10):
        payload, residual = codec.encode(delta, residual)
        total += np.asarray(codec.decode(payload)["w"][0])
    np.testing.assert_allclose(total[1], 10 * tiny, rtol=0.3)


@pytest.mark.parametrize("flavor,qmax,rel", [("e4m3", 448.0, 2.0 ** -4),
                                             ("e5m2", 57344.0, 2.0 ** -3)])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_fp8_codec_error_bound(flavor, qmax, rel, use_kernel):
    """Per element: |dec(enc(x)) - x| <= |x| * half-ulp(flavor) + scale
    (the scale term covers the subnormal region near zero)."""
    delta = _tree(seed=6, scale=0.1)
    codec = Fp8Codec(use_kernel=use_kernel, flavor=flavor)
    payload, _ = codec.encode(delta)
    assert payload.codec == ("fp8" if flavor == "e4m3" else "fp8_e5m2")
    assert payload.scales is not None
    back = codec.decode(payload)
    for key in delta:
        x = np.asarray(delta[key]).reshape(3, -1)
        b = np.asarray(back[key]).reshape(3, -1)
        assert np.asarray(payload.data[key]).dtype.itemsize == 1
        for i in range(3):
            s = max(np.abs(x[i]).max(), 1e-12) / qmax
            assert (np.abs(b[i] - x[i]) <= np.abs(x[i]) * rel + s).all()


@pytest.mark.parametrize("flavor", ["e4m3", "e5m2"])
def test_fp8_error_feedback_residual_is_the_roundtrip_error(flavor):
    delta = _tree(seed=7)
    residual = jax.tree.map(jnp.zeros_like, delta)
    codec = Fp8Codec(flavor=flavor)
    payload, new_res = codec.encode(delta, residual)
    back = codec.decode(payload)
    for key in delta:
        np.testing.assert_allclose(
            np.asarray(new_res[key]),
            np.asarray(delta[key]) - np.asarray(back[key]), atol=1e-6)


def test_fp8_error_feedback_recovers_accumulated_truncation():
    """e4m3's smallest subnormal is 2^-9: with amax 1.0 the scale is 1/448,
    so anything below ~2.2e-6 truncates to zero every round without error
    feedback but accumulates in the residual and ships with it."""
    big, tiny = 1.0, 1e-6
    delta = {"w": jnp.asarray([[big, tiny]])}
    codec = Fp8Codec()
    shipped = codec.decode(codec.encode(delta)[0])
    assert float(shipped["w"][0, 1]) == 0.0
    residual = {"w": jnp.zeros((1, 2))}
    total = np.zeros(2)
    for _ in range(10):
        payload, residual = codec.encode(delta, residual)
        total += np.asarray(codec.decode(payload)["w"][0])
    np.testing.assert_allclose(total[1], 10 * tiny, rtol=0.5)


def test_payload_nbytes_counts_wire_dtype_and_scales():
    delta = {"w": jnp.zeros((2, 16))}
    assert F32Passthrough().encode(delta)[0].nbytes() == 2 * 16 * 4
    assert BF16Cast().encode(delta)[0].nbytes() == 2 * 16 * 2
    # int8/fp8: 1 byte/elem + one f32 scale per worker row
    assert Int8Symmetric().encode(delta)[0].nbytes() == 2 * 16 + 2 * 4
    assert Fp8Codec().encode(delta)[0].nbytes() == 2 * 16 + 2 * 4


def test_make_codec_aliases_and_unknown():
    assert make_codec("float32").name == "f32"
    assert make_codec("bf16").name == "bf16"
    assert make_codec("int8").width == 1
    for spelling in ("fp8", "float8", "e4m3", "fp8_e4m3"):
        c = make_codec(spelling)
        assert c.name == "fp8" and c.width == 1 and c.qdtype == "fp8_e4m3"
    for spelling in ("e5m2", "fp8_e5m2"):
        c = make_codec(spelling)
        assert c.name == "fp8_e5m2" and c.qdtype == "fp8_e5m2"
    with pytest.raises(ValueError):
        make_codec("fp4")


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("qd", ["int8", "fp8_e4m3", "fp8_e5m2"])
def test_codec_scale_shapes_scalar_and_empty_sentinel_leaves(qd, use_kernel):
    """Regression: the (K, 1, ...) keepdims scale contract assumed >=1-d
    tensors — scalar params (0-d) must quantize elementwise with a 0-d
    scale, and 0-size sentinel leaves must pass through with unit scales
    instead of producing NaN scales from an empty amax."""
    codec = make_codec(qd if qd != "fp8_e4m3" else "fp8",
                       use_kernel=use_kernel)
    delta = {"w": jnp.asarray([[0.25, -1.0], [3.0, 0.5]]),
             "scalar": jnp.asarray(0.75),
             "sentinel": jnp.zeros((2, 0))}
    residual = jax.tree.map(jnp.zeros_like, delta)
    payload, new_res = codec.encode(delta, residual)
    assert payload.scales["w"].shape == (2, 1)
    assert payload.scales["scalar"].shape == ()
    assert payload.scales["sentinel"].shape == (2, 1)
    assert not np.isnan(np.asarray(payload.scales["sentinel"])).any()
    back = codec.decode(payload)
    for key in delta:
        assert back[key].shape == delta[key].shape
        assert new_res[key].shape == delta[key].shape
    assert np.asarray(back["sentinel"]).size == 0
    # a scalar is its own amax, so it lands exactly on the top bucket
    np.testing.assert_allclose(float(back["scalar"]), 0.75, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_res["scalar"]),
                               0.75 - np.asarray(back["scalar"]), atol=1e-7)


# ---------------------------------------------------------------------------
# Pallas kernels vs jnp oracle
# ---------------------------------------------------------------------------

_jit_oracle = jax.jit(reference_quantize_ef, static_argnames=("dtype",))


@pytest.mark.parametrize("shape", [(2, 128), (3, 5, 7), (1, 100), (4,),
                                   (2, 64, 3)])
def test_quantize_kernel_matches_oracle(shape):
    ks = jax.random.split(jax.random.key(sum(shape)), 2)
    x = jax.random.normal(ks[0], shape) * 0.05
    r = jax.random.normal(ks[1], shape) * 0.005
    q, nr, s = quantize_ef(x, r, interpret=True)
    qr, nrr, sr = _jit_oracle(x, r)
    # the wire (payload + scales) is bit-identical to the compiled oracle:
    # the amax is a max (order-free) and the quantize arithmetic is the
    # oracle's, op for op
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    tol = float(np.max(np.asarray(sr))) * 1.5 + 1e-9
    np.testing.assert_allclose(np.asarray(nr), np.asarray(nrr), atol=tol)
    out = dequantize(q, s, interpret=True)
    ref = reference_dequantize(qr, sr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


def test_quantize_kernel_no_residual_path():
    x = jax.random.normal(jax.random.key(9), (2, 40)) * 0.1
    q, nr, s = quantize_ef(x, interpret=True)
    qr, nrr, _ = reference_quantize_ef(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(nr), np.asarray(nrr), atol=1e-7)


@pytest.mark.parametrize("dtype", ["fp8_e4m3", "fp8_e5m2"])
@pytest.mark.parametrize("shape", [(2, 128), (3, 5, 7), (1, 100), (4,)])
def test_quantize_kernel_matches_oracle_fp8(dtype, shape):
    """Same contract as the int8 sweep for both fp8 flavors: scales agree
    to reduction-order noise and the dequantized payloads agree within one
    quantization level."""
    ks = jax.random.split(jax.random.key(sum(shape) + len(dtype)), 2)
    x = jax.random.normal(ks[0], shape) * 0.05
    r = jax.random.normal(ks[1], shape) * 0.005
    q, nr, s = quantize_ef(x, r, dtype=dtype, interpret=True)
    qr, nrr, sr = _jit_oracle(x, r, dtype=dtype)
    assert q.dtype == qr.dtype and q.dtype.itemsize == 1
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    out = dequantize(q, s, interpret=True)
    ref = reference_dequantize(qr, sr)
    rel = 2.0 ** -3 if dtype == "fp8_e4m3" else 2.0 ** -2
    tol = float(np.max(np.abs(np.asarray(ref)))) * rel \
        + float(np.max(np.asarray(sr)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)
    np.testing.assert_allclose(np.asarray(nr), np.asarray(nrr), atol=tol)


# ---------------------------------------------------------------------------
# Strategy integration
# ---------------------------------------------------------------------------

def _setup(k=2, h=4, **dkw):
    cfg = tiny_cfg("dense")
    m = build_model(cfg)
    params, _ = init_params(cfg, jax.random.key(0))
    dcfg = DiLoCoConfig(num_workers=k, h_inner_steps=h, **dkw)
    return cfg, m, params, dcfg


def _data(cfg, k, step, B=4, S=16):
    key = jax.random.key(1000 + step)
    toks = jax.random.randint(key, (k, B, S), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": (toks + 1) % cfg.vocab_size}


def _run(m, params, dcfg, strategy, cfg, steps, k):
    dt = DistTrainer(m.loss, OPT, dcfg, strategy)
    state = dt.init(params)
    return dt.run(state, lambda s: _data(cfg, k, s), steps)


def test_pipelined_f1_delay0_matches_diloco_exactly():
    """One fragment covering everything, applied at the boundary — the
    pipelined runner degenerates bit-for-bit to DiLoCoSync."""
    cfg, m, params, dcfg = _setup(k=2, h=4)
    a_state, a_hist = _run(m, params, dcfg, DiLoCoSync(), cfg, 12, k=2)
    b_state, b_hist = _run(m, params, dcfg,
                           PipelinedSync(num_fragments=1, delay=0), cfg,
                           12, k=2)
    for x, y in zip(jax.tree.leaves(a_state.global_params),
                    jax.tree.leaves(b_state.global_params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [s for s, _ in b_hist["frag_syncs"]] == a_hist["sync_steps"]
    np.testing.assert_array_equal(a_hist["loss"], b_hist["loss"])


def test_pipelined_fragments_rotate_and_converge():
    cfg, m, params, dcfg = _setup(k=2, h=4)
    state, hist = _run(m, params, dcfg,
                       PipelinedSync(num_fragments=2, delay=2), cfg, 16, k=2)
    assert np.isfinite(hist["loss"]).all()
    assert hist["loss"][-1] < hist["loss"][0]
    # boundary at 3,7,11,15 -> applies at 5,9,13, final flushed by finalize
    assert hist["frag_syncs"] == [(5, 0), (9, 1), (13, 0), (15, 1)]


def test_pipelined_rejects_bad_delay():
    cfg, m, params, dcfg = _setup(k=2, h=4)
    dt = DistTrainer(m.loss, OPT, dcfg, PipelinedSync(delay=4))
    state = dt.init(params)
    with pytest.raises(ValueError):
        dt.run(state, lambda s: _data(cfg, 2, s), 4)


def test_int8_error_feedback_tracks_f32_loss():
    """Acceptance: the int8 error-feedback toy run matches the f32 final
    loss within 2%."""
    cfg, m, params, dcfg = _setup(k=2, h=4)
    _, f32_hist = _run(m, params, dcfg, DiLoCoSync(), cfg, 20, k=2)
    dcfg8 = DiLoCoConfig(num_workers=2, h_inner_steps=4, delta_dtype="int8")
    _, i8_hist = _run(m, params, dcfg8, DiLoCoSync(), cfg, 20, k=2)
    rel = abs(i8_hist["loss"][-1] - f32_hist["loss"][-1]) \
        / f32_hist["loss"][-1]
    assert rel < 0.02, rel


def test_fp8_error_feedback_tracks_f32_loss():
    """The fp8 (e4m3) error-feedback toy run matches the f32 final loss
    within 2% — same acceptance bar as int8."""
    cfg, m, params, dcfg = _setup(k=2, h=4)
    _, f32_hist = _run(m, params, dcfg, DiLoCoSync(), cfg, 20, k=2)
    dcfg8 = DiLoCoConfig(num_workers=2, h_inner_steps=4, delta_dtype="fp8")
    _, fp8_hist = _run(m, params, dcfg8, DiLoCoSync(), cfg, 20, k=2)
    rel = abs(fp8_hist["loss"][-1] - f32_hist["loss"][-1]) \
        / f32_hist["loss"][-1]
    assert rel < 0.02, rel


def test_streaming_int8_error_feedback_converges():
    cfg, m, params, _ = _setup()
    dcfg = DiLoCoConfig(num_workers=2, h_inner_steps=4, delta_dtype="int8")
    _, hist = _run(m, params, dcfg, StreamingSync(num_fragments=2), cfg,
                   12, k=2)
    assert np.isfinite(hist["loss"]).all()
    assert hist["loss"][-1] < hist["loss"][0]


def test_make_strategy_pipelined_and_seed():
    s = make_strategy(DiLoCoConfig(strategy="pipelined", num_fragments=8,
                                   sync_delay=5))
    assert s.name == "pipelined" and s.num_fragments == 8 and s.delay == 5
    s = make_strategy(DiLoCoConfig(strategy="overlapped", sync_seed=42))
    assert s.seed == 42


def test_codec_aware_payload_schedules():
    """Acceptance: int8 pipelined fragments ship >= 8x fewer bytes than f32
    blocking DiLoCo over the same step budget."""
    n, steps, h = 1_000_000, 400, 100
    f32 = DiLoCoConfig(h_inner_steps=h)
    i8 = DiLoCoConfig(h_inner_steps=h, delta_dtype="int8")
    base = sum(e.bytes_per_worker
               for e in DiLoCoSync().payload_schedule(n, steps, f32))
    pipe = PipelinedSync(num_fragments=4, delay=h // 2)
    events = pipe.payload_schedule(n, steps, i8)
    assert all(e.codec == "int8" and e.kind == "fragment" for e in events)
    assert all(e.apply_step - e.step == h // 2 for e in events)
    got = sum(e.bytes_per_worker for e in events)
    assert base / got >= 8, (base, got)
    # bf16 halves f32; fragment ids rotate
    bf = DiLoCoConfig(h_inner_steps=h, delta_dtype="bfloat16")
    bf_bytes = sum(e.bytes_per_worker
                   for e in DiLoCoSync().payload_schedule(n, steps, bf))
    assert bf_bytes * 2 == base
    assert [e.fragment for e in events] == [0, 1, 2, 3]


def test_fp8_pipelined_ships_half_the_int8_bytes():
    """The BENCH_train acceptance arm, as a unit statement: fp8 wire width
    equals int8's, so doubling the fragment count (one n/F fragment per
    outer round) halves the boundary bytes exactly."""
    n, steps, h = 1_000_000, 400, 100
    i8 = DiLoCoConfig(h_inner_steps=h, delta_dtype="int8")
    f8 = DiLoCoConfig(h_inner_steps=h, delta_dtype="fp8")
    i8_ev = PipelinedSync(num_fragments=4,
                          delay=h // 2).payload_schedule(n, steps, i8)
    f8_ev = PipelinedSync(num_fragments=8,
                          delay=h // 2).payload_schedule(n, steps, f8)
    assert all(e.codec == "fp8" for e in f8_ev)
    i8_bytes = sum(e.bytes_per_worker for e in i8_ev)
    f8_bytes = sum(e.bytes_per_worker for e in f8_ev)
    assert i8_bytes == 2 * f8_bytes, (i8_bytes, f8_bytes)


# ---------------------------------------------------------------------------
# Heterogeneous simulator + calibration
# ---------------------------------------------------------------------------

def _delta_events(n=200, every=5, steps=10, window=0):
    return [SyncEvent(step=s, bytes_per_worker=n, kind="delta",
                      apply_step=s + window)
            for s in range(every - 1, steps, every)]


def test_heterogeneous_reduces_to_symmetric_on_equal_speeds():
    comm = CommModel(bandwidth=100.0, latency=0.0)
    events = _delta_events()
    a = simulate_schedule(events, 10, 1.0, comm)
    b = simulate_heterogeneous(events, 10, [1.0, 1.0, 1.0], comm)
    assert b["wall_clock_s"] == pytest.approx(a["wall_clock_s"])
    assert b["stall_s"] == pytest.approx(a["stall_s"])
    assert b["total_bytes"] == a["total_bytes"]
    assert b["straggler_s"] == 0.0


def test_heterogeneous_straggler_sets_the_pace():
    comm = CommModel(bandwidth=1e12, latency=0.0)  # comm ~free
    events = _delta_events()
    r = simulate_heterogeneous(events, 10, [1.0, 1.0, 1.5], comm)
    assert r["wall_clock_s"] == pytest.approx(15.0)
    assert r["straggler_s"] == pytest.approx(5.0)


def test_bounded_staleness_hides_transfer():
    """A 2s transfer due at its emit step stalls the fleet 2s; two steps of
    staleness budget hide it entirely."""
    comm = CommModel(bandwidth=100.0, latency=0.0)
    events = [SyncEvent(step=4, bytes_per_worker=200, kind="delta",
                        apply_step=4)]
    blocked = simulate_heterogeneous(events, 10, [1.0, 1.0], comm,
                                     staleness_steps=0)
    assert blocked["stall_s"] == pytest.approx(2.0)
    relaxed = simulate_heterogeneous(events, 10, [1.0, 1.0], comm,
                                     staleness_steps=2)
    assert relaxed["stall_s"] == 0.0
    assert relaxed["wall_clock_s"] == pytest.approx(10.0)


def test_bytes_by_codec_breakdown():
    comm = CommModel(bandwidth=100.0, latency=0.0)
    events = [SyncEvent(step=0, bytes_per_worker=100, kind="delta",
                        apply_step=0, codec="int8"),
              SyncEvent(step=1, bytes_per_worker=400, kind="delta",
                        apply_step=1, codec="f32")]
    r = simulate_schedule(events, 2, 1.0, comm)
    assert r["bytes_by_codec"] == {"int8": 100.0, "f32": 400.0}


def test_load_calibration_from_dryrun_json(tmp_path):
    entries = [
        {"arch": "nanochat-d20", "step_kind": "diloco-inner",
         # flops-bound: 197e12 peak -> 1.0s; hbm term 1e9/819e9 ~ 1.2ms
         "analytic": {"total_flops": 197e12, "bytes": 1e9}},
        {"arch": "nanochat-d20", "step_kind": "diloco-outer",
         "shape": "outer[int8]",
         "collectives_weighted": {"wire_bytes_per_device": 5e9,
                                  "cross_pod_bytes_per_device": 2.2e9}},
        {"arch": "other", "step_kind": "diloco-inner", "measured_step_s": 9.9,
         "analytic": {}},
    ]
    path = tmp_path / "dryrun_outer.json"
    path.write_text(json.dumps(entries))
    cal = load_calibration(str(path), arch="nanochat-d20")
    assert cal is not None
    assert cal.step_time_s == pytest.approx(1.0)   # flops / PEAK_FLOPS_BF16
    assert cal.sync_bytes_per_worker == pytest.approx(2.2e9)
    assert cal.sync_dtype == "int8"   # parsed from the outer[...] shape tag
    # measured seconds take precedence over the roofline derivation
    other = load_calibration(str(path), arch="other")
    assert other.step_time_s == pytest.approx(9.9)
    assert load_calibration(str(path), arch="missing") is None
    assert load_calibration(str(tmp_path / "nope.json")) is None


def test_modeled_step_time_calibration_precedence():
    assert modeled_step_time(1e15) > 0
    cal = CommCalibration(step_time_s=0.123)
    assert modeled_step_time(1e15, calibration=cal) == 0.123
    assert modeled_step_time(1e15,
                             calibration=CommCalibration()) == \
        modeled_step_time(1e15)
