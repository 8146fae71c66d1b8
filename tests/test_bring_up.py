"""Fast CPU checks of what keeps the chip path honest: the chip smoke
test refuses a CPU backend, kernels interpret on the CPU and nowhere
else, the CLIs reach published widths, and the compile cache stays where
it is put."""
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_default_interpret_is_cpu_only_and_takes_no_override(monkeypatch):
    from repro.kernels.common import (default_interpret, pallas_mode,
                                      resolve_interpret)
    assert jax.default_backend() == "cpu"
    for value in ("0", "false", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert default_interpret() is True
        assert pallas_mode() == "interpret"
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False      # explicit callers only


def test_nanochat_d20_is_the_published_relu2_mlp():
    from repro.configs import get_config
    cfg = get_config("nanochat-d20")
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (20, 1280, 5120, 65536)
    assert cfg.mlp_activation == "relu2"
    assert math.isclose(cfg.param_count() / 1e6, 561.04, abs_tol=0.01)


@pytest.mark.parametrize("reduced", [False, True])
def test_make_model_vocab(reduced):
    """Published configs keep their vocab (the tokenizer's ids are a
    subset); reduced ones take the tokenizer's."""
    from repro.launch.train import make_model
    cfg, _ = make_model("nanochat-d20", reduced, 512)
    assert cfg.vocab_size == (512 if reduced else 65536)
    assert cfg.d_model == (256 if reduced else 1280)


def test_reduced_is_opt_in_on_both_clis(monkeypatch):
    import types
    import repro.launch.compile_cache as cc
    import repro.launch.serve as serve
    import repro.launch.train as train
    seen = {}
    monkeypatch.setattr(cc, "setup_compile_cache", lambda: "")
    monkeypatch.setattr(train, "run_pipeline",
                        lambda **kw: seen.setdefault("train", kw["reduced"]))
    train.main(["--arch", "nanochat-d20"])

    def stop_at_model(arch, reduced, vocab):
        seen["serve"] = reduced
        raise SystemExit(0)
    tok = types.SimpleNamespace(vocab_size=512)
    monkeypatch.setattr(train, "build_pipeline",
                        lambda: (None, tok, None, None))
    monkeypatch.setattr(train, "make_model", stop_at_model)
    with pytest.raises(SystemExit):
        serve.main(["--config", "nanochat-d20", "--prompt", "hi"])
    assert seen == {"train": False, "serve": False}


def test_compile_cache_directory(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV, str(tmp_path))
        assert cc.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(cc.ENV)
        assert cc.setup_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
