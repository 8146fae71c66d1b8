"""Compile rehearsal of the main-path Pallas kernels for one described TPU
v5e chip at nanochat-d20 widths (head_dim 128, 10 KV heads, d_ff 5120,
vocab 65536), and of the training path's flash attention at d20's and
Qwen1.5-0.5B's head widths.  Nothing runs: Mosaic and XLA:TPU compile for a chip that is
described, not attached, and refuse what the chip would refuse — tiling
violations and VMEM overruns that interpret-mode tests cannot see.

The topology is described only inside a fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# nanochat-d20 serving shapes: 8 slots, 2048 tokens each in 16-token blocks
S, KV, G, D, BS, MB = 8, 10, 1, 128, 16, 128
NB = S * MB
T = 5                                  # spec_k=4 verify: carry + 4 drafts


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel
    return compiled


POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_paged_attention_compiles_at_d20(one_chip, kind, pool):
    from repro.kernels.decode_attention import (
        paged_decode_attention, paged_decode_attention_dequant,
        paged_verify_attention, paged_verify_attention_dequant)
    pdt = POOL_DTYPES[pool]
    q = ((S, KV, G, D) if kind == "decode" else (S, T, KV, G, D),
         jnp.float32 if pool == "int8" else pdt)
    kv = ((NB, BS, KV, D), pdt)
    scales = [((NB, BS, KV), jnp.float32)] * 2 if pool == "int8" else []
    meta = [((S, MB), jnp.int32), ((S,), jnp.int32)]
    if kind == "verify":
        meta.append(((S,), jnp.int32))
    fn = {("decode", False): paged_decode_attention,
          ("decode", True): paged_decode_attention_dequant,
          ("verify", False): paged_verify_attention,
          ("verify", True): paged_verify_attention_dequant}[
              (kind, pool == "int8")]
    _compile(lambda *a: fn(*a, interpret=False), one_chip,
             q, kv, kv, *scales, *meta)


@pytest.mark.parametrize("dtype", ["int8", "fp8_e4m3"])
def test_quantize_compiles_at_d20(one_chip, dtype):
    """K=4 worker rows of the d20 MLP matrix: quantize_ef and dequantize."""
    from repro.kernels.quantize import dequantize, quantize_ef, target_dtype
    leaf = (4, 1280, 5120)
    _compile(lambda x, r: quantize_ef(x, r, dtype=dtype, interpret=False),
             one_chip, (leaf, jnp.float32), (leaf, jnp.float32))
    _compile(lambda q, s: dequantize(q, s, interpret=False), one_chip,
             (leaf, target_dtype(dtype)), ((4, 1, 1), jnp.float32))


def test_fused_adamw_compiles_on_d20_embedding(one_chip):
    from repro.kernels.fused_adamw import fused_adamw_update
    leaf = ((65536, 1280), jnp.float32)
    scalar = ((), jnp.float32)
    _compile(lambda p, g, m, v, lr, b1, b2: fused_adamw_update(
        p, g, m, v, lr, b1, b2, b1=0.9, b2=0.95, eps=1e-10, wd=0.0,
        interpret=False), one_chip, leaf, leaf, leaf, leaf,
        scalar, scalar, scalar)


@pytest.mark.parametrize("H,D", [(10, 128), (16, 64)])   # d20, Qwen1.5-0.5B
def test_flash_attention_grad_compiles(one_chip, H, D):
    """jax.grad through the flash kernel's custom_vjp at one training row of
    2,048 tokens, with the block attention() picks there: the forward, the
    dK/dV sweep and the dQ sweep each compile through Mosaic."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import flash_block
    S = 2048
    blk = flash_block(S)

    def grads(q, k, v, do):
        def loss(q, k, v):
            o = flash_attention(q, k, v, bq=blk, bk=blk, interpret=False)
            return jnp.sum(o * do)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = _compile(grads, one_chip, *[((1, H, S, D), jnp.float32)] * 4)
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 3
