"""``correct`` on the CPU at a small size: a sound run passes the cells'
limits; the timed path broken underneath, once for each fault a training
cell can have, fails them; the precision control fails them too."""
import time

import pytest

import harness
import run
import tiny
import train

SEED = 2**31 + 11
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _measure(cell):
    return run.measure(cell, SEED, 1.0, False, harness.checkout_root(),
                       DEVICE, time.perf_counter())


@pytest.fixture(params=[False, True], ids=["relu2", "swiglu_bias_tied"])
def train_cell(request):
    return tiny.train_cell(qwen_like=request.param)


def test_sound_train_run_is_correct(train_cell):
    out = _measure(train_cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loss_gap", "moment_gap", "change_gap"}


def _unchanged(monkeypatch):
    from repro.core.diloco import DiLoCoTrainer
    orig = DiLoCoTrainer.inner_step

    def inner_step(self, state, batches):
        new, loss, metrics = orig(self, state, batches)
        return state._replace(inner_step=new.inner_step), loss, metrics
    monkeypatch.setattr(DiLoCoTrainer, "inner_step", inner_step)


def _half_batch(monkeypatch):
    from repro.models import transformer
    orig = transformer.lm_loss

    def lm_loss(params, batch, cfg):
        half = batch["tokens"].shape[-1] // 2
        return orig(params, {k: v[..., :half] for k, v in batch.items()},
                    cfg)
    monkeypatch.setattr(transformer, "lm_loss", lm_loss)


def _no_exchange(monkeypatch):
    from repro.core.diloco import DiLoCoTrainer
    monkeypatch.setattr(DiLoCoTrainer, "outer_step_ef",
                        lambda self, state, residual=None: (state, residual))


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _no_exchange],
                         ids=["state_unchanged", "half_batch", "no_exchange"])
def test_broken_train_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _measure(tiny.train_cell())
    assert not out["correct"], out["checks"]


def test_precision_control_is_not_correct():
    """The control of the training cells: the reference with fp8 matmul
    operands, put in the program's place, fails the cell's limits that the
    program passes."""
    cell = tiny.train_cell()
    conf, t = cell.config, cell.traffic
    m, B, S = conf["run_as"], conf["train"]["batch"], conf["train"]["seq"]
    ref = train.follow_reference(m, t, B, S, SEED)
    prog = harness.judge(train.compare(train.start(cell, SEED)[3], ref),
                         cell.limits)
    ctrl = harness.judge(train.compare(
        train.follow_reference(m, t, B, S, SEED, dtype="fp8"), ref),
        cell.limits)
    assert harness.all_within(prog), prog
    assert not harness.all_within(ctrl), ctrl
