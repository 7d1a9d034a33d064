"""Device milliseconds per step in the ``encode`` scope of the train step:
the wire's encode (bucketize, the uniform draw, the quantize kernel, the
pack).  Read from the traced window's device operations, joined to the
compiled step's scopes (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "encode")
