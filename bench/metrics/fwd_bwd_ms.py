"""Device milliseconds per step in the ``fwd_bwd`` scope of the train step:
the forward and backward pass (``jax.value_and_grad`` of the loss, the
micro-batch scan included).  Read from the traced window's device
operations, joined to the compiled step's scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "fwd_bwd")
