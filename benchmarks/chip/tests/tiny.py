"""Cells of the benchmark cut to a size a CPU test can hold: the real
configuration, traffic and limits files, a two-layer model of the same
kind."""
import json

import harness

TINY = {"num_layers": 2, "d_model": 64, "num_heads": 2, "num_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab_size": 512,
        "mlp_activation": "relu2", "qkv_bias": False, "tie_embeddings": False,
        "rope_theta": 10000.0, "norm_eps": 1e-5}


# cell -> (configuration, traffic mix, limits file)
CELLS = {"d20-train-diloco": ("nanochat-d20", "train-diloco",
                              "d20-train-diloco")}


def _read(*parts):
    return json.loads(harness.HERE.joinpath(*parts).read_text())


def cell_from_files(name):
    """The cell as its files describe it, whether or not BENCHMARK.json
    lists it yet."""
    config, traffic, limits = CELLS[name]
    return harness.Cell(name, 1, _read("configs", f"{config}.json"),
                        _read("traffic", f"{traffic}.json"),
                        _read("limits", f"{limits}.json"), [], [])


def train_cell(name="d20-train-diloco", qwen_like=False):
    """The cell at the tiny size; ``qwen_like`` swaps in the other dense
    decoder kind the reference and weights cover (SwiGLU, QKV bias, tied
    embeddings)."""
    cell = cell_from_files(name)
    run_as = dict(TINY)
    if qwen_like:
        run_as.update(mlp_activation="swiglu", qkv_bias=True,
                      tie_embeddings=True)
    cell.config = dict(cell.config, run_as=run_as,
                       train={"batch": 1, "seq": 64})
    return cell
