"""Multi-device semantics, run in subprocesses with 8 fake CPU devices
(XLA_FLAGS must be set before jax import, and only for these tests —
the rest of the suite sees one device):

  * quantized allreduce (both wire modes) is unbiased and all workers
    agree bit-exactly;
  * FSDP + fp32 reduce-scatter reproduces pure-DP fp32 gradients;
  * a reduced multi-pod dry-run (2x2x2 mesh) lowers and compiles for a
    train and a decode shape.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_script(body: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_quantized_allreduce_unbiased_and_consistent():
    out = run_script(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist.sync import quantized_allreduce
from repro.core.schemes import QuantScheme

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 2048)) * 0.01
fp = g.mean(0)
scheme = QuantScheme(name="alq", bits=4, bucket_size=256)
state = scheme.init_state()
for mode in ("all_gather", "two_phase"):
    def f(gl, key):
        out, _ = quantized_allreduce(gl.reshape(-1), scheme, state, key,
                                     axes=("pod", "data"), mode=mode)
        return out
    # out_specs P(None): replicated output -> jax checks all-device agreement
    smf = jax.jit(jax.shard_map(f, mesh=mesh,
        in_specs=(P(("pod", "data")), P()), out_specs=P(), check_vma=False))
    outs = [np.asarray(smf(g, jax.random.PRNGKey(i))) for i in range(24)]
    est = np.mean(outs, 0)
    err = np.abs(est - np.asarray(fp)).max()
    one = np.abs(outs[0] - np.asarray(fp)).max()
    assert err < one / 2.5, (mode, err, one)
print("SYNC_OK")
""")
    assert "SYNC_OK" in out


def test_fsdp_fp32_matches_pure_dp():
    out = run_script(r"""
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

def grads_for(param_mode):
    m = Model(cfg, tp=2, dp=4, param_mode=param_mode,
              fsdp_scheme=QuantScheme(name="fp32", bucket_size=256),
              fsdp_sync="fp32")
    params = m.init(jax.random.PRNGKey(42))
    pspecs = m.param_specs()
    def lossf(p, i, l):
        loss = m.loss(p, {"ids": i, "labels": l})
        g = jax.grad(lambda q: m.loss(q, {"ids": i, "labels": l}))(p)
        g.pop("final_norm")  # replicated leaf: compared via flat parts only
        if param_mode == "dp":
            gf = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(g)])
            gf = jax.lax.psum(gf, ("data",)) / 4
        else:
            gf = jnp.concatenate([x.reshape(-1) for x in jax.tree.leaves(g)])
        return loss, gf
    f = jax.jit(jax.shard_map(lossf, mesh=mesh,
        in_specs=(pspecs, P("data"), P("data")),
        out_specs=(P(), P() if param_mode == "dp" else P(("data",))),
        check_vma=False))
    return f(params, ids, labels)

l_dp, g_dp = grads_for("dp")
l_fs, g_fs = grads_for("fsdp")
# identical init => identical loss
np.testing.assert_allclose(float(l_dp), float(l_fs), rtol=1e-5)
# gradient *norms* agree (layouts differ: dp tree vs fsdp flat+padding)
n_dp = float(jnp.sqrt(jnp.sum(g_dp**2)))
n_fs = float(jnp.sqrt(jnp.sum(g_fs**2)))
np.testing.assert_allclose(n_dp, n_fs, rtol=1e-3)
print("FSDP_OK", n_dp, n_fs)
""")
    assert "FSDP_OK" in out


@pytest.mark.slow
def test_reduced_multipod_dryrun():
    out = run_script(r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.configs.shapes import InputShape
from repro.launch import dryrun

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = configs.get_smoke_config("llama3.2-1b")
for shape in (InputShape("t", 64, 8, "train"),
              InputShape("d", 128, 8, "decode")):
    compiled, acost, tl, tc = dryrun.lower_pair(cfg, shape, mesh, bits=3)
    assert compiled.cost_analysis() is not None
    assert acost.flops > 0
print("DRYRUN_OK")
""")
    assert "DRYRUN_OK" in out
