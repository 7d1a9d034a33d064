"""Share of the traced window's busy device time spent in operations
that carry none of the train step's layer scopes (``bench/scopes.py``):
what the per-layer times leave unexplained."""
from bench import scopes


def read(run):
    seconds = scopes.per_chip(run)
    if seconds is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds.get(None, 0.0) / run.trace["busy_s"]
