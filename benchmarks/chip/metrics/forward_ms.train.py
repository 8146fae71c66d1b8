"""Device milliseconds per inner step of the model's forward pass: self
time of the operations under the ``model`` scope that autodiff did not
transpose (``scopes.py``)."""
import scopes


def read(run):
    return scopes.per_step_ms(run, "model")
