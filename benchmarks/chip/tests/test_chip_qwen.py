"""The ``qwen05b-train-diloco`` cell on the CPU: its configuration file
against the program at the published widths (shapes only, nothing
allocated), its operation counts, and ``correct`` under its own limits at
a tiny size of the same kind, where a planted fault and the precision
control fail them."""
import flops
import harness
import tiny
import train
import weights
from test_chip_correct import SEED, _half_batch, _measure

CELL = "qwen05b-train-diloco"
# what the configuration file fixes besides the sizes: the block's kind
KINDS = ("mlp_activation", "qkv_bias", "tie_embeddings", "rope_theta",
         "norm_eps")


def _cell():
    return harness.load_cell(CELL)


def test_run_as_is_the_published_model():
    conf = _cell().config
    m = conf["run_as"]
    assert (m["num_layers"], m["d_model"], m["num_heads"],
            m["num_kv_heads"], m["head_dim"], m["d_ff"],
            m["vocab_size"]) == (24, 1024, 16, 16, 64, 2816, 151936)
    assert {k: m[k] for k in KINDS} == {
        "mlp_activation": "swiglu", "qkv_bias": True, "tie_embeddings": True,
        "rope_theta": 1e6, "norm_eps": 1e-6}
    assert conf["reduced"] == []


def test_layout_matches_the_program_at_published_widths():
    conf = _cell().config
    cfg = harness.model_config(conf)
    assert (cfg.norm_eps, cfg.rope_theta, cfg.loss_chunk) == (1e-6, 1e6, 512)
    harness.check_layout(cfg, weights.spec(conf["run_as"]))


def test_operation_counts():
    m = _cell().config["run_as"]
    assert flops.param_count(m) == 463_987_712
    assert flops.train_flops_per_token(m, 2048) == 3_387_906_048


def _tiny_cell():
    """The cell with its own files, at the tiny size but the file's own
    kind of block."""
    cell = _cell()
    kinds = {k: cell.config["run_as"][k] for k in KINDS}
    cell.config = dict(cell.config, run_as=dict(tiny.TINY, **kinds),
                       train={"batch": 1, "seq": 64})
    return cell


def test_sound_run_is_correct_under_its_limits():
    out = _measure(_tiny_cell())
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"loss_gap", "moment_gap", "change_gap"}


def test_half_batch_is_not_correct_under_its_limits(monkeypatch):
    _half_batch(monkeypatch)
    out = _measure(_tiny_cell())
    assert not out["correct"], out["checks"]


def test_precision_control_is_not_correct_under_its_limits():
    """The fp8 control in the program's place fails the cell's limits
    that the program passes."""
    cell = _tiny_cell()
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
