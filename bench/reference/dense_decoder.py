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

``precision`` is "highest" (float32 matrix products, the reference) or
"fp8" (each product's operands rounded to float8 e4m3, and the gradient
flowing back into it to e5m2, each with one scale per tensor, as float8
training does: the control that a lower-precision path has to fail).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# largest finite float8 e4m3 and e5m2 values
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


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


def _fp8(x, dtype, top):
    """x rounded to a float8 ``dtype`` under one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(spec, a, b):
    """A product as float8 training computes it: the operands in e4m3,
    and in the backward pass the incoming gradient in e5m2."""
    return _einsum(spec, _fp8(a, jnp.float8_e4m3fn, _E4M3_MAX),
                   _fp8(b, jnp.float8_e4m3fn, _E4M3_MAX))


def _fp8_dot_fwd(spec, a, b):
    a8 = _fp8(a, jnp.float8_e4m3fn, _E4M3_MAX)
    b8 = _fp8(b, jnp.float8_e4m3fn, _E4M3_MAX)
    return _einsum(spec, a8, b8), (a8, b8)


def _fp8_dot_bwd(spec, res, g):
    a8, b8 = res
    _, pullback = jax.vjp(lambda a, b: _einsum(spec, a, b), a8, b8)
    return pullback(_fp8(g, jnp.float8_e5m2, _E5M2_MAX))


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def _dot(spec: str, a, b, precision: str):
    if precision == "fp8":
        return _fp8_dot(spec, a, b)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return _einsum(spec, a, b)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate the first and second halves of each head (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(cfg, s, p, x, precision):
    B, S, d = x.shape
    eps = cfg["rms_norm_eps"]
    res = cfg.get("residual_multiplier", 1.0)
    scale = cfg.get("attention_multiplier", s["hd"] ** -0.5)
    h = _rms(x, p["norm1"], eps)
    m = p["mixer"]
    q = _dot("bsd,de->bse", h, m["wq"], precision).reshape(
        B, S, s["H"], s["hd"])
    k = _dot("bsd,de->bse", h, m["wk"], precision).reshape(
        B, S, s["KV"], s["hd"])
    v = _dot("bsd,de->bse", h, m["wv"], precision).reshape(
        B, S, s["KV"], s["hd"])
    if cfg.get("qk_norm"):
        q = _rms(q, m["q_norm"], eps)
        k = _rms(k, m["k_norm"], eps)
    q = _rope(q, cfg["rope_theta"])
    k = _rope(k, cfg["rope_theta"])
    rep = s["H"] // s["KV"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = _dot("bqhd,bkhd->bhqk", q, k, precision) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _dot("bhqk,bkhd->bqhd", probs, v, precision).reshape(B, S, -1)
    x = x + res * _dot("bse,ed->bsd", att, m["wo"], precision)
    h = _rms(x, p["norm2"], eps)
    f = p["ffn"]
    gate = jax.nn.silu(_dot("bsd,df->bsf", h, f["w1"], precision))
    up = _dot("bsd,df->bsf", h, f["w3"], precision)
    return x + res * _dot("bsf,fd->bsd", gate * up, f["w2"], precision)


def loss(cfg: dict, params: dict, ids, labels, precision: str = "highest"):
    """Mean next-token cross-entropy over every position of the batch."""
    s = sizes(cfg)
    x = params["embed"][0][ids] * cfg.get("embedding_multiplier", 1.0)
    for slot in params["slots"]:
        layers = jax.tree.map(lambda a: a[:, 0], slot)

        def body(x, p):
            return _block(cfg, s, p, x, precision), None

        x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = _dot("bsd,dv->bsv", x, params["lm_head"][0], precision)
    logits = logits / cfg.get("logits_scaling", 1.0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
