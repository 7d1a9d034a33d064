"""Inputs made from ``--seed``: token batches and random keys.

The token source is a copy of the program's uniform generator
(``repro.train.data.Pipeline(kind="uniform")``), kept here so that a
change to the program cannot move the yardstick: step ``t`` of seed
``s`` draws its (B, S + 1) ids from ``SeedSequence([s, t, 0xD1CE])``, and
the ids and labels are the sequence shifted by one.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# key streams drawn from one seed
WEIGHTS, ROUNDING, REFERENCE, CANDIDATE = range(4)


def batch(job: dict, vocab: int, seed: int, step: int) -> dict:
    if job["tokens"] != "uniform":
        raise ValueError(f"unknown token source {job['tokens']!r}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0xD1CE]))
    B, S = job["global_batch"], job["seq_len"]
    toks = rng.integers(0, vocab, size=(B, S + 1), dtype=np.int32)
    return {"ids": toks[:, :-1], "labels": toks[:, 1:]}


def key(seed: int, stream: int):
    """A raw uint32[2] JAX key for one stream of ``seed`` (any whole
    number: the seed enters a ``SeedSequence``, not an int32)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)
