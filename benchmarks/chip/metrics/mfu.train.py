"""Model FLOP/s utilization of training: model operations per token
(``flops.train_flops_per_token``: forward + backward, no recomputation, no
optimizer) times the traced window's tokens per second, over the chip's
bf16 peak."""


def read(run):
    if not run.get("tokens"):
        return None
    rate = run["tokens"] / run["window_s"]
    return 100.0 * run["flops_per_token"] * rate / run["peak_flops"]
