"""Device milliseconds per step in the ``step_metrics`` scope of the train
step: the step's output counters (the loss's mean over workers, the
gradient norm, the wire's bits).  Read from the traced window's device
operations, joined to the compiled step's scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "step_metrics")
