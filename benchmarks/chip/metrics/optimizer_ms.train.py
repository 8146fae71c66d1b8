"""Device milliseconds per inner step of the inner optimizer: self time of
the operations under the ``inner_opt`` scope (clipping, Muon with its
Newton-Schulz iterations, AdamW, and the update's application;
``scopes.py``)."""
import scopes


def read(run):
    return scopes.per_step_ms(run, "inner_opt")
