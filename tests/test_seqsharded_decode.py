"""Sequence-sharded decode correctness on a real multi-device mesh
(subprocess, 8 fake devices): the distributed-softmax KV-cache read with
the cache sharded over (data x model) must reproduce the same mesh's
full-sequence forward logits — this is the long_500k serving path."""
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_seq_sharded_decode_matches_forward():
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import Model, ModelConfig
from repro.models.layers import lm_head_logits, rms_norm

cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                  num_heads=8, num_kv_heads=2, d_ff=128, vocab_size=256,
                  compute_dtype="float32")
S, n_dec, max_len = 24, 3, 32
ids = jax.random.randint(jax.random.PRNGKey(1), (1, S + n_dec), 0, 256)

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
# batch-1 long-context layout: cache sequence sharded over data AND model
m = Model(cfg, tp=2, dp=4, data_axes=("data",),
          seq_shard_axes=("data", "model"))
params = m.init(jax.random.PRNGKey(0))
pspecs = m.param_specs()
shards = 8
cspecs = m.cache_pspecs(())
bspec = P()  # batch 1: replicated over data

def full_logits(p, i):
    x, _ = m.forward(p, i)
    x = rms_norm(x, p["final_norm"], cfg.norm_eps)
    return lm_head_logits(m.ctx, p["lm_head"].squeeze(0), x[:, -1],
                          cfg.vocab_size)

with jax.set_mesh(mesh):
    ref = jax.jit(jax.shard_map(full_logits, in_specs=(pspecs, bspec),
                                out_specs=bspec, check_vma=False))
    pf = jax.jit(jax.shard_map(
        lambda p, i: m.prefill(p, i, max_len=max_len, cache_shards=shards),
        in_specs=(pspecs, bspec), out_specs=(bspec, cspecs),
        check_vma=False))
    df = jax.jit(jax.shard_map(
        lambda p, t, pos, c: m.decode(p, t, pos, c, cache_shards=shards),
        in_specs=(pspecs, bspec, bspec, cspecs),
        out_specs=(bspec, cspecs), check_vma=False))

    logits, caches = pf(params, ids[:, :S])
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref(params, ids[:, :S])),
                               rtol=3e-4, atol=3e-4, err_msg="prefill")
    for t in range(S, S + n_dec):
        logits, caches = df(params, ids[:, t],
                            jnp.full((1,), t, jnp.int32), caches)
        want = ref(params, ids[:, : t + 1])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=4e-3, atol=4e-3,
                                   err_msg=f"step {t}")
print("SEQSHARD_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"OUT:{proc.stdout}\nERR:{proc.stderr}"
    assert "SEQSHARD_OK" in proc.stdout
