"""FSDP with the *quantized* reduce-scatter backward: unbiasedness of the
gradient estimate vs the fp32 FSDP path (subprocess, 8 devices)."""
import os
import subprocess
import sys

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_fsdp_quantized_grads_unbiased():
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import Model, ModelConfig
from repro.core.schemes import QuantScheme

cfg = ModelConfig(name="t", arch_type="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                  compute_dtype="float32")
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 256)
labels = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
scheme = QuantScheme(name="alq", bits=8, bucket_size=256)

def grads_for(sync, key_seed):
    m = Model(cfg, tp=2, dp=4, param_mode="fsdp", fsdp_scheme=scheme,
              fsdp_sync=sync)
    params = m.init(jax.random.PRNGKey(42))
    pspecs = m.param_specs()
    sync_ctx = (scheme.init_state().levels, jax.random.PRNGKey(key_seed))
    def gradf(p, i, l):
        g = jax.grad(lambda q: m.loss(q, {"ids": i, "labels": l},
                                      sync_ctx))(p)
        return jnp.concatenate([g["slots"][0].reshape(-1)])
    f = jax.jit(jax.shard_map(gradf, mesh=mesh,
        in_specs=(pspecs, P("data"), P("data")),
        out_specs=P(("data",)), check_vma=False))
    return np.asarray(f(params, ids, labels))

ref = grads_for("fp32", 0)
qs = np.mean([grads_for("quantized", s) for s in range(6)], axis=0)
# 8-bit quantized RS, averaged over keys, approaches the fp32 RS result
denom = np.abs(ref).max() + 1e-9
rel = np.abs(qs - ref).max() / denom
assert rel < 0.08, rel
print("FSDP_Q_OK", rel)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"OUT:{proc.stdout}\nERR:{proc.stderr}"
    assert "FSDP_Q_OK" in proc.stdout
