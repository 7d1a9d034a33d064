"""Substrate coverage: checkpointing, the jaxpr cost model, data
pipeline determinism/learnability, mesh helpers, and FSDP flatten
metadata round-trips."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import fsdp as fsdp_lib
from repro.launch import jaxpr_cost
from repro.train import checkpoint
from repro.train.data import DataConfig, Pipeline


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "nested": {"b": jnp.ones((2,), jnp.bfloat16),
                   "c": jnp.asarray(3, jnp.int32)},
    }
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, tree)
    like = jax.tree.map(jnp.zeros_like, tree)
    restored = checkpoint.restore(path, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, {"a": jnp.zeros((3,))})
    with pytest.raises(ValueError):
        checkpoint.restore(path, {"a": jnp.zeros((4,))})


def test_jaxpr_cost_exact_matmul_flops():
    def f(a, b):
        return a @ b

    a = jnp.zeros((64, 128))
    b = jnp.zeros((128, 32))
    c = jaxpr_cost.analyze_fn(f, a, b)
    assert c.flops == 2 * 64 * 128 * 32
    # bytes: operands + result
    assert c.hbm_bytes == (64 * 128 + 128 * 32 + 64 * 32) * 4


def test_jaxpr_cost_multiplies_scan_trip_count():
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    x = jnp.zeros((16, 16))
    ws = jnp.zeros((10, 16, 16))
    c = jaxpr_cost.analyze_fn(f, x, ws)
    assert c.flops == 10 * 2 * 16 * 16 * 16


def test_jaxpr_cost_counts_collectives_inside_scan():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    def f(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "data"), None
        out, _ = jax.lax.scan(body, jnp.zeros((8,)), xs)
        return out

    with jax.set_mesh(mesh):
        sm = jax.shard_map(f, in_specs=P(), out_specs=P(),
                           check_vma=False)
        c = jaxpr_cost.analyze_fn(sm, jnp.zeros((5, 8)))
    # 5 iterations x 8 floats x 4 bytes x weight 2.0
    assert c.collective_bytes == 5 * 8 * 4 * 2.0


def test_markov_pipeline_deterministic_and_learnable():
    cfg = DataConfig(kind="markov", vocab_size=64, seq_len=32,
                     global_batch=4, seed=7)
    p1, p2 = Pipeline(cfg), Pipeline(cfg)
    b1, b2 = p1.batch(3), p2.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["ids"]),
                                  np.asarray(b2["ids"]))
    # labels are next tokens
    np.testing.assert_array_equal(np.asarray(b1["ids"][:, 1:]),
                                  np.asarray(b1["labels"][:, :-1]))
    # learnable: bigram entropy well below uniform
    big = p1.batch(0)
    H_uniform = np.log(64)
    logp = np.log(p1.table[np.asarray(big["ids"]).reshape(-1),
                           np.asarray(big["labels"]).reshape(-1)])
    assert -logp.mean() < H_uniform - 0.5


def test_fsdp_flatten_meta_roundtrip():
    specs = {"w": ((4, 6), 4), "b": ((6,), 0), "sub": {"u": ((2, 3), 2)}}
    meta = fsdp_lib.flatten_meta(specs)
    n = fsdp_lib.flat_size(meta)
    assert n == 24 + 6 + 6
    flat = jnp.arange(n, dtype=jnp.float32)
    tree = fsdp_lib.unflatten(flat, meta, jnp.float32)
    # order is deterministic (sorted names): b, sub/u, w
    assert tree["b"].shape == (6,)
    assert tree["sub"]["u"].shape == (2, 3)
    assert tree["w"].shape == (4, 6)
    rebuilt = jnp.concatenate(
        [tree["b"].reshape(-1), tree["sub"]["u"].reshape(-1),
         tree["w"].reshape(-1)])
    np.testing.assert_array_equal(np.asarray(rebuilt), np.asarray(flat))


def test_chunk_plan_alignment():
    for n, bucket, M in [(10_000, 256, 8), (1 << 20, 8192, 16),
                         (123, 64, 4), (8192 * 32, 8192, 32)]:
        k, nb_p = fsdp_lib.chunk_plan(n, bucket, M)
        assert nb_p * bucket >= n
        assert nb_p % (M * k) == 0


def test_mesh_helpers():
    from repro.launch.mesh import make_local_mesh, mesh_axes
    mesh = make_local_mesh(tp=1)
    data_axes, model_axis = mesh_axes(mesh)
    assert model_axis == "model"
    assert data_axes == ("data",)
    from jax.sharding import AxisType
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_roofline_peaks_keyed_on_device_kind():
    from repro.launch import hlo_analysis
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND
    peaks = hlo_analysis.peaks_for(PRODUCTION_DEVICE_KIND)
    roof = hlo_analysis.Roofline(197e12, 819e9, 50e9, {}, peaks)
    assert (roof.compute_s, roof.memory_s, roof.collective_s) == (
        1.0, 1.0, 1.0)
    with pytest.raises(ValueError, match="no published peaks"):
        hlo_analysis.peaks_for("cpu")
