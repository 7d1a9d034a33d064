"""Serving correctness: prefill + N decode steps reproduce the full-
sequence forward logits for every attention flavour and recurrent
family (KV-cache ring addressing, RWKV/Mamba state carry, cross-attn)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import Model, ModelConfig
from repro.models.layers import lm_head_logits, rms_norm

BASE = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            d_ff=128, vocab_size=256, compute_dtype="float32")

CONFIGS = [
    ModelConfig(name="dense", arch_type="dense", **BASE),
    ModelConfig(name="sliding", arch_type="dense", attn_kind="sliding",
                window=8, **BASE),
    ModelConfig(name="chunked", arch_type="dense", attn_kind="chunked",
                chunk=8, **BASE),
    ModelConfig(name="rwkv", arch_type="ssm", layer_pattern="rwkv",
                rwkv_head_dim=32, **BASE),
    ModelConfig(name="hybrid-moe", arch_type="hybrid",
                layer_pattern="mamba_hybrid", attn_every=2, moe=True,
                num_experts=4, top_k=2, moe_every=2, capacity_factor=8.0,
                **{**BASE, "num_layers": 4}),
    ModelConfig(name="vlm", arch_type="vlm", cross_attn_every=2, **BASE),
]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c.name)
def test_prefill_decode_matches_forward(cfg):
    S, n_decode, max_len = 24, 3, 64
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    m = Model(cfg, tp=1, dp=1)
    params = m.init(jax.random.PRNGKey(0))
    B = 2
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S + n_decode), 0,
                             cfg.vocab_size)
    vision = None
    vspec = None
    if cfg.cross_attn_every:
        vision = jax.random.normal(jax.random.PRNGKey(3),
                                   (B, 8, cfg.d_model), jnp.float32)
        vspec = P("data")
    pspecs = m.param_specs()
    cspec = jax.tree.map(lambda _: P(),
                         jax.eval_shape(lambda: m.init_cache(B, max_len, 1)))

    def full_logits(p, ids, vision):
        x, _ = m.forward(p, ids, vision)
        x = rms_norm(x, p["final_norm"], cfg.norm_eps)
        return lm_head_logits(m.ctx, p["lm_head"].squeeze(0), x[:, -1],
                              cfg.vocab_size)

    with jax.set_mesh(mesh):
        smap = lambda f, i, o: jax.jit(
            jax.shard_map(f, in_specs=i, out_specs=o, check_vma=False))
        ref = smap(full_logits, (pspecs, P("data"), vspec), P("data"))
        pf = smap(lambda p, i, v: m.prefill(p, i, v, max_len=max_len,
                                            cache_shards=1),
                  (pspecs, P("data"), vspec), (P("data"), cspec))
        df = smap(lambda p, t, pos, c, v: m.decode(p, t, pos, c, v,
                                                   cache_shards=1),
                  (pspecs, P("data"), P("data"), cspec, vspec),
                  (P("data"), cspec))

        logits, caches = pf(params, ids[:, :S], vision)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref(params, ids[:, :S], vision)),
            rtol=3e-4, atol=3e-4)
        for t in range(S, S + n_decode):
            logits, caches = df(params, ids[:, t],
                                jnp.full((B,), t, jnp.int32), caches,
                                vision)
            want = ref(params, ids[:, : t + 1], vision)
            np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                       rtol=4e-3, atol=4e-3,
                                       err_msg=f"{cfg.name} step {t}")
