"""Plain float32 reference of a dense decoder-only transformer.

Covers Qwen3 (grouped-query attention with RMS-normed queries and keys)
and Granite 3.0 (grouped-query attention with scalar multipliers on the
embedding, the attention scores, the residual branches and the logits),
as their published descriptions have them: pre-norm blocks, RMSNorm,
rotary embeddings that rotate the two halves of each head, causal
softmax attention, a SwiGLU feed-forward block and an untied LM head
under a mean token cross-entropy.

It imports nothing of the program under test.  It reads its sizes from
a configuration file of ``bench/configs`` and its weights as a plain
dict in the layout the program stores them in (``init_params`` makes
them from a seed), with the layers stacked on a leading axis:

    embed (1, V, d), lm_head (1, d, V), final_norm (d,),
    slots: [{norm1, norm2: (L, 1, d),
             mixer: {wq (L, 1, d, H*hd), wk, wv (L, 1, d, KV*hd),
                     wo (L, 1, H*hd, d)[, q_norm, k_norm (L, 1, hd)]},
             ffn: {w1, w3 (L, 1, d, F), w2 (L, 1, F, d)}}]

``precision`` is "highest" (the reference) or "fp8" (the control), as
``bench.reference.ops.dot`` computes them.  A configuration file names
this module with ``"reference": "dense_decoder"``; it exports the
interface that ``bench.reference.of`` looks for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.ops import dot, rms, rope


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // H
    return dict(d=d, H=H, KV=cfg["num_key_value_heads"], hd=hd,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def param_shapes(cfg: dict) -> dict:
    """Shapes of every weight, in the program's storage layout."""
    s = sizes(cfg)
    d, H, KV, hd, F, V, L = (s[k] for k in ("d", "H", "KV", "hd", "F",
                                            "V", "L"))
    mixer = {"wq": (L, 1, d, H * hd), "wk": (L, 1, d, KV * hd),
             "wv": (L, 1, d, KV * hd), "wo": (L, 1, H * hd, d)}
    if cfg.get("qk_norm"):
        mixer["q_norm"] = (L, 1, hd)
        mixer["k_norm"] = (L, 1, hd)
    return {
        "embed": (1, V, d), "lm_head": (1, d, V), "final_norm": (d,),
        "slots": [{"norm1": (L, 1, d), "norm2": (L, 1, d), "mixer": mixer,
                   "ffn": {"w1": (L, 1, d, F), "w3": (L, 1, d, F),
                           "w2": (L, 1, F, d)}}],
    }


def _fan_in(path: tuple, shape: tuple, d: int) -> int | None:
    """None for a norm weight (initialized to ones)."""
    name = path[-1]
    if name.endswith("norm") or name in ("norm1", "norm2"):
        return None
    if name == "embed":
        return d
    return shape[-2]


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: N(0, 1/fan_in) matrices, an N(0, 1/d) embedding
    and unit norm weights, each leaf from its own fold of ``key``."""
    d = sizes(cfg)["d"]
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        names = tuple(getattr(p, "key", getattr(p, "idx", None))
                      for p in path)
        fan = _fan_in(names, shape, d)
        if fan is None:
            leaves.append(jnp.ones(shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
                          * fan ** -0.5)
    return jax.tree.unflatten(tree, leaves)


def _block(cfg, s, p, x, precision):
    B, S, d = x.shape
    eps = cfg["rms_norm_eps"]
    res = cfg.get("residual_multiplier", 1.0)
    scale = cfg.get("attention_multiplier", s["hd"] ** -0.5)
    h = rms(x, p["norm1"], eps)
    m = p["mixer"]
    q = dot("bsd,de->bse", h, m["wq"], precision).reshape(
        B, S, s["H"], s["hd"])
    k = dot("bsd,de->bse", h, m["wk"], precision).reshape(
        B, S, s["KV"], s["hd"])
    v = dot("bsd,de->bse", h, m["wv"], precision).reshape(
        B, S, s["KV"], s["hd"])
    if cfg.get("qk_norm"):
        q = rms(q, m["q_norm"], eps)
        k = rms(k, m["k_norm"], eps)
    q = rope(q, cfg["rope_theta"])
    k = rope(k, cfg["rope_theta"])
    rep = s["H"] // s["KV"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = dot("bqhd,bkhd->bhqk", q, k, precision) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = dot("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, S, -1)
    x = x + res * dot("bse,ed->bsd", att, m["wo"], precision)
    h = rms(x, p["norm2"], eps)
    f = p["ffn"]
    gate = jax.nn.silu(dot("bsd,df->bsf", h, f["w1"], precision))
    up = dot("bsd,df->bsf", h, f["w3"], precision)
    return x + res * dot("bsf,fd->bsd", gate * up, f["w2"], precision)


def loss(cfg: dict, params: dict, ids, labels, precision: str = "highest"):
    """Mean next-token cross-entropy over every position of the batch."""
    s = sizes(cfg)
    x = params["embed"][0][ids] * cfg.get("embedding_multiplier", 1.0)
    for slot in params["slots"]:
        layers = jax.tree.map(lambda a: a[:, 0], slot)

        def body(x, p):
            return _block(cfg, s, p, x, precision), None

        x, _ = jax.lax.scan(body, x, layers)
    x = rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = dot("bsd,dv->bsv", x, params["lm_head"][0], precision)
    logits = logits / cfg.get("logits_scaling", 1.0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token, without recomputation: 2
    per weight of every matrix product (LM head included, the embedding
    lookup not), plus causal attention's two products over (S + 1) / 2
    positions on average; backward twice forward."""
    s = sizes(cfg)
    d, H, KV, hd, F, V, L = (s[k] for k in ("d", "H", "KV", "hd", "F",
                                            "V", "L"))
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    matmul = L * per_layer + d * V
    attention = L * 2 * 2 * H * hd * (seq_len + 1) / 2
    return 3.0 * (2.0 * matmul + attention)


def program_checks(cfg: dict, m) -> dict:
    """{name: (program's value, file's value)} for the program's model
    configuration ``m``: the sizes and options this module computes
    with, and that the model is dense (no experts, no other mixer)."""
    return {
        "hidden_size": (m.d_model, cfg["hidden_size"]),
        "intermediate_size": (m.d_ff, cfg["intermediate_size"]),
        "num_attention_heads": (m.num_heads, cfg["num_attention_heads"]),
        "num_key_value_heads": (m.num_kv_heads, cfg["num_key_value_heads"]),
        "head_dim": (m.head_dim_, cfg["head_dim"]),
        "num_hidden_layers": (m.num_layers, cfg["num_hidden_layers"]),
        "vocab_size": (m.vocab_size, cfg["vocab_size"]),
        "rope_theta": (float(m.rope_theta), float(cfg["rope_theta"])),
        "rms_norm_eps": (m.norm_eps, cfg["rms_norm_eps"]),
        "qk_norm": (m.qk_norm, cfg["qk_norm"]),
        "attention_bias": (m.qkv_bias, cfg["attention_bias"]),
        "dense": (m.arch_type == "dense" and not m.moe, True),
    }
