"""The training step names its parts for JAX's profiler: device scopes in
the compiled programs' ``op_name`` metadata, host spans in the chunked
loop, and neither changes what is computed."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_cfg
from repro.configs.base import DiLoCoConfig, OptimizerConfig
from repro.core import DiLoCoSync, DistTrainer
from repro.models.transformer import build_model, init_params

CFG = tiny_cfg("dense", loss_chunk=8)
MODEL = build_model(CFG)
OPT = OptimizerConfig(total_steps=100, warmup_steps=0, schedule="constant",
                      learning_rate=0.02, adam_lr=1e-3)
DCFG = DiLoCoConfig(num_workers=2, h_inner_steps=2)
STEPS = 4                    # two chunks of H = 2, each ending in a sync
INNER_SCOPES = ("model", "attention", "mlp", "lm_head", "inner_opt", "clip",
                "muon", "newton_schulz", "adamw")
SPANS = ("trainer.data", "trainer.chunk", "trainer.fetch", "trainer.sync")


def _data(step):
    toks = jax.random.randint(jax.random.key(500 + step), (2, 2, 16), 0,
                              CFG.vocab_size)
    return {"tokens": toks, "labels": (toks + 1) % CFG.vocab_size}


def _trainer():
    return DistTrainer(MODEL.loss, OPT, DCFG, DiLoCoSync())


def _op_names(lowered) -> list:
    return re.findall(r'op_name="([^"]*)"',
                      lowered.as_text(dialect="hlo", debug_info=True))


def _has_scope(names, scope: str) -> bool:
    """``scope`` is a segment of some op's path, bare or wrapped by a
    transform (``vmap(jvp(model))``)."""
    rx = re.compile(rf"(^|[/(;]){re.escape(scope)}($|[/);])")
    return any(rx.search(n) for n in names)


@pytest.fixture(scope="module")
def programs():
    dt = _trainer()
    eng = dt.engine()
    state = dt.init(init_params(CFG, jax.random.key(0))[0])
    batches = jax.tree.map(lambda *x: jnp.stack(x), _data(0), _data(1))
    inner = _op_names(jax.jit(eng.inner_chunk).lower(state, batches))
    outer = _op_names(jax.jit(eng.outer_step_ef).lower(state))
    return inner, outer


@pytest.mark.parametrize("scope", INNER_SCOPES)
def test_inner_chunk_carries_scope(programs, scope):
    inner, outer = programs
    assert _has_scope(inner, scope), scope
    assert not _has_scope(outer, scope), scope


def test_outer_step_program_carries_scope(programs):
    inner, outer = programs
    assert _has_scope(outer, "outer_step")
    assert not _has_scope(inner, "outer_step")


def test_backward_is_named_by_autodiff(programs):
    """The backward runs as ``transpose(jvp(model))``, the remat
    recompute inside it: the rule the trace reduction classifies by."""
    inner, _ = programs
    assert any("transpose(jvp(model))" in n for n in inner)
    assert any("jvp(model)" in n and "transpose(" not in n for n in inner)


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = ProfileData.from_file(path)
    events = [ev for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events]
    return sorted(events, key=lambda ev: ev.start_ns)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The same run twice from the same weights, the second under the
    profiler."""
    params = init_params(CFG, jax.random.key(0))[0]
    dt = _trainer()
    plain = dt.run(dt.init(params), _data, STEPS)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        traced = dt.run(dt.init(params), _data, STEPS)
    return plain, traced, _host_events(log_dir)


def test_chunk_spans_in_order(runs):
    *_, events = runs
    spans = [ev for ev in events if ev.name in SPANS]
    assert [ev.name for ev in spans] == list(SPANS) * (STEPS // 2)
    chunks = [dict(ev.stats) for ev in spans if ev.name == "trainer.chunk"]
    assert [(c["first_step"], c["steps"]) for c in chunks] == [(0, 2),
                                                                (2, 2)]


def test_one_step_annotation_per_chunk(runs):
    *_, events = runs
    steps = [dict(ev.stats) for ev in events if ev.name == "train"]
    assert [s["step_num"] for s in steps] == [0, 1]


def test_profiler_changes_nothing(runs):
    (s0, h0), (s1, h1), _ = runs
    assert h0["loss"] == h1["loss"]
    assert h0["sync_steps"] == h1["sync_steps"]
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
