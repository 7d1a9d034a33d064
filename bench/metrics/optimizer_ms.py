"""Device milliseconds per step in the ``optimizer`` scope of the train
step: the optimizer's update (``apply_updates``).  Read from the traced
window's device operations, joined to the compiled step's scopes
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "optimizer")
