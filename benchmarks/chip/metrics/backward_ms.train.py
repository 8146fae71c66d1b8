"""Device milliseconds per inner step of the model's backward pass: self
time of the operations under ``transpose(model)``, the name autodiff gives
the backward, the layer scan's remat recompute included (``scopes.py``)."""
import scopes


def read(run):
    return scopes.per_step_ms(run, "transpose(model)")
