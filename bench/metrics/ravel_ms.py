"""Device milliseconds per step in the ``ravel`` scope of the train step:
the flat gradient's two copies (``ravel_pytree`` of the gradients and
``unravel`` of the synced vector).  A copy that XLA fuses into its
consumer counts in the consumer's layer.  Read from the traced window's
device operations, joined to the compiled step's scopes
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "ravel")
