"""Data pipeline.

Two sources, both deterministic and host-side (the container is offline):

  * ``markov``: sequences sampled from a fixed random bigram table — a
    *learnable* synthetic LM task, so integration tests and examples can
    assert the loss actually decreases;
  * ``uniform``: i.i.d. uniform tokens (throughput/dry-run filler) —
    the source for published vocabularies, whose bigram table would not
    fit in host memory (vocab^2 float64: 185 GB at V = 151,936).

Batches are yielded as already-global arrays; the launcher shards them
over the DP mesh axes with ``jax.device_put`` + NamedSharding.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


# largest vocabulary whose vocab x vocab float64 bigram table the
# markov source builds (134 MB)
MARKOV_MAX_VOCAB = 4096


@dataclasses.dataclass(frozen=True)
class DataConfig:
    kind: str = "markov"        # markov | uniform
    vocab_size: int = 256
    seq_len: int = 128
    global_batch: int = 8
    seed: int = 0
    markov_temperature: float = 0.5


class Pipeline:
    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        if cfg.kind == "markov":
            if cfg.vocab_size > MARKOV_MAX_VOCAB:
                raise ValueError(
                    f"the markov source builds a vocab x vocab bigram "
                    f"table; vocab {cfg.vocab_size} > {MARKOV_MAX_VOCAB} "
                    f"would take {cfg.vocab_size ** 2 * 8 / 1e9:.0f} GB "
                    f"of host memory — use kind='uniform'")
            logits = rng.standard_normal((cfg.vocab_size, cfg.vocab_size))
            logits /= cfg.markov_temperature
            p = np.exp(logits - logits.max(-1, keepdims=True))
            self.table = (p / p.sum(-1, keepdims=True)).astype(np.float64)
        else:
            self.table = None

    def batch(self, step: int):
        """Returns dict(ids (B,S) int32, labels (B,S) int32)."""
        cfg = self.cfg
        rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, step, 0xD1CE]))
        B, S, V = cfg.global_batch, cfg.seq_len, cfg.vocab_size
        if cfg.kind == "uniform":
            toks = rng.integers(0, V, size=(B, S + 1), dtype=np.int32)
        else:
            toks = np.empty((B, S + 1), np.int32)
            toks[:, 0] = rng.integers(0, V, size=B)
            u = rng.random((B, S))
            cdf = np.cumsum(self.table, axis=-1)
            for t in range(S):
                toks[:, t + 1] = np.argmax(
                    u[:, t, None] < cdf[toks[:, t]], axis=-1)
        return {
            "ids": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:]),
        }

    def vision_stub(self, num_tokens: int, d_model: int, step: int):
        """Precomputed patch/frame embeddings (the modality-frontend stub
        allowed by the assignment for [vlm]/[audio] archs)."""
        cfg = self.cfg
        rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, step, 0xFACE]))
        x = rng.standard_normal(
            (cfg.global_batch, num_tokens, d_model)).astype(np.float32)
        return jnp.asarray(x)
