"""Plain references the benchmark holds the program to.

A configuration file of ``bench/configs`` names its architecture with
``"reference": "<module>"``: ``bench/reference/<module>.py``, which
imports nothing of the program and exports

    param_shapes(cfg)      the program's parameter tree, each leaf the
                           shape of one weight as a tuple
    init_params(cfg, key)  that tree of float32 weights, from a key
    loss(cfg, params, ids, labels, precision)
                           the mean token cross-entropy of a batch, with
                           ``precision`` "highest" (the reference) or
                           "fp8" (the control)
    flops_per_token(cfg, seq_len)
                           forward and backward FLOPs per token, in
                           closed form
    program_checks(cfg, m) {name: (program's value, file's value)} for
                           the program's model configuration ``m``,
                           read from its attributes: every size and
                           option the module computes with

``of`` finds it.  ``wire``, ``adamw`` and ``ops`` serve every
architecture and are none.
"""
from __future__ import annotations

import importlib

from bench.spec import NAME

INTERFACE = ("param_shapes", "init_params", "loss", "flops_per_token",
             "program_checks")


def of(cfg: dict):
    """The architecture module that the configuration ``cfg`` names.

    Raises KeyError where the name is missing or is no module name, or
    where the module lacks part of the interface, and FileNotFoundError
    where no such module exists."""
    name = cfg.get("reference")
    if not (isinstance(name, str) and NAME.match(name)
            and name.isidentifier()):
        raise KeyError(f"configuration {cfg.get('name')!r} names no "
                       f"reference module: {name!r}")
    qualified = f"{__name__}.{name}"
    try:
        mod = importlib.import_module(qualified)
    except ModuleNotFoundError as e:
        if e.name != qualified:
            raise
        raise FileNotFoundError(f"no reference module bench/reference/"
                                f"{name}.py") from None
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise KeyError(f"bench/reference/{name}.py is not an architecture: "
                       f"it lacks {missing}")
    return mod
