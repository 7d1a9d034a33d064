"""Device milliseconds per step in the ``decode`` scope of the train step:
the wire's decode and average (unpack, the dequantize kernel, the mean
over workers).  Read from the traced window's device operations, joined
to the compiled step's scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "decode")
