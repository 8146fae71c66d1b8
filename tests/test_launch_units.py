"""Unit tests for the launch layer: logical sharding resolution, profiles,
registry variants, analytic estimators."""
from jax.sharding import PartitionSpec as P

from repro.configs import (SHAPES, decode_cache_capacity, get_config,
                           input_specs, long_context_variant)
from repro.launch.analytic import bytes_per_device, flops_per_device
from repro.launch.dryrun_lib import PROFILES, auto_profile
from repro.models.sharding import spec_for, sharding_ctx


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_spec_for_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    with sharding_ctx(None):
        pass
    # 25 heads cannot shard 16-way -> None; 4096 seq unsharded by default
    spec = spec_for(("batch", "seq", "heads"), (256, 4096, 25), mesh)
    assert spec == P("data", None, None)
    spec = spec_for(("batch", "seq", "heads"), (256, 4096, 32), mesh)
    assert spec == P("data", None, "model")
    # axis used once only
    spec = spec_for(("model", "ffn"), (1024, 4096), mesh)
    assert spec == P("model", None)


def test_qwen15_05b_published_keys():
    """The registry entry runs Qwen1.5-0.5B's published config.json
    (Qwen2ForCausalLM): sizes, SwiGLU, QKV bias, tied table, rotary
    theta and RMSNorm eps; full attention (no sliding window)."""
    c = get_config("qwen1.5-0.5b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.resolved_head_dim(), c.d_ff, c.vocab_size) == \
        (24, 1024, 16, 16, 64, 2816, 151936)
    assert c.mlp_activation == "swiglu" and c.qkv_bias and c.tie_embeddings
    assert c.rope_theta == 1e6 and c.norm_eps == 1e-6
    assert c.window == 0 and not c.window_pattern


def test_long_context_variant_subquadratic():
    for aid in ("command-r-plus-104b", "mistral-large-123b", "qwen1.5-0.5b"):
        cfg = long_context_variant(get_config(aid))
        assert cfg.window or cfg.window_pattern, aid
    ssm = long_context_variant(get_config("mamba2-1.3b"))
    assert ssm.window == 0  # untouched
    mix = long_context_variant(get_config("mixtral-8x7b"))
    assert mix.window == 4096  # native SWA kept


def test_decode_cache_capacity():
    long = SHAPES["long_500k"]
    dec = SHAPES["decode_32k"]
    cfg = long_context_variant(get_config("mistral-large-123b"))
    assert decode_cache_capacity(cfg, long) == 8192        # ring buffer
    assert decode_cache_capacity(get_config("mistral-large-123b"), dec) == 32768


def test_input_specs_shapes():
    cfg = get_config("internvl2-26b")
    sp = input_specs(cfg, SHAPES["train_4k"])
    assert sp["tokens"].shape == (256, 4096 - 256)
    assert sp["patches"].shape == (256, 256, 6144)
    cfg = get_config("seamless-m4t-medium")
    sp = input_specs(cfg, SHAPES["prefill_32k"])
    assert sp["frames"].shape == (32, 1024, 1024)
    sp = input_specs(cfg, SHAPES["decode_32k"])
    assert sp["token"].shape == (128, 1)


def test_auto_profile_selection():
    tp = 16
    assert auto_profile(get_config("qwen1.5-0.5b"), SHAPES["train_4k"], tp) \
        == PROFILES["dp"]
    assert auto_profile(get_config("mamba2-1.3b"), SHAPES["train_4k"], tp) \
        == PROFILES["dp_fsdp"]
    l4 = auto_profile(get_config("llama4-scout-17b-a16e"),
                      SHAPES["prefill_32k"], tp)
    assert l4.get("expert") == ("model",)
    ml = auto_profile(get_config("mistral-large-123b"), SHAPES["train_4k"], tp)
    assert ml.get("seq") == ("model",)
    # decode untouched
    assert auto_profile(get_config("qwen1.5-0.5b"), SHAPES["decode_32k"], tp) \
        == {}
    # measured regressions stay excluded: dp on small-batch prefill,
    # attention-DP for kv-only indivisibility
    assert auto_profile(get_config("qwen1.5-0.5b"), SHAPES["prefill_32k"],
                        tp) == {}
    assert auto_profile(get_config("nemotron-4-15b"), SHAPES["train_4k"],
                        tp) == {}


def test_analytic_flops_scale_with_layers_and_tokens():
    cfg = get_config("qwen1.5-0.5b")
    f1 = flops_per_device(cfg, SHAPES["train_4k"], 256)
    f2 = flops_per_device(cfg.with_(num_layers=48), SHAPES["train_4k"], 256)
    assert f2["total_flops"] > 1.7 * f1["total_flops"]
    # 6ND sanity: within 3x of the analytic total for training
    assert 0.3 < f1["model_flops_6nd"] / f1["total_flops"] < 3.0
    # decode flops are ~tokens/step smaller
    fd = flops_per_device(cfg, SHAPES["decode_32k"], 256)
    assert fd["total_flops"] < f1["total_flops"] / 1e3


def test_analytic_bytes_monotonic():
    cfg = get_config("qwen1.5-0.5b")
    b1 = bytes_per_device(cfg, SHAPES["train_4k"], 256)["bytes"]
    b2 = bytes_per_device(cfg.with_(num_layers=48), SHAPES["train_4k"], 256)["bytes"]
    assert b2 > b1
    bd = bytes_per_device(cfg, SHAPES["decode_32k"], 256,
                          cache_capacity=32768)["bytes"]
    assert bd > 0


def test_auto_flag_resolves_in_dryrun_rules():
    """The __auto__ sentinel must be consumed and replaced by the per-arch
    profile (regression: the sweep once ran with the sentinel ignored)."""
    from repro.launch.dryrun_lib import auto_profile, PROFILES
    rules = {"__auto__": True}
    eff = dict(rules)
    assert eff.pop("__auto__", False)
    got = auto_profile(get_config("qwen1.5-0.5b"), SHAPES["train_4k"], 16)
    assert got == PROFILES["dp"]
