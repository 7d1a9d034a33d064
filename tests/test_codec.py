"""GradientCodec coverage (docs/wire_format.md, "Codec layer").

* uniform round trip at every width 1..8 x {fp32, fp16} norms: the
  payload decodes to exactly Q(v), and the plan's word counts match the
  payload shapes;
* mixed-width round trip: decode(encode) equals a per-bucket reference
  that quantizes each bucket on its own resampled grid;
* constant-width MixedWidthCodec == UniformCodec values (the layouts
  differ, the math must not);
* sharded payloads: diagonal decode of one's own sharded payload equals
  the unsharded values; traced-shard decode (``lax.switch`` under a
  named vmap axis) agrees with the static per-shard decode;
* MixedWidthCodec end to end: ``quantized_allreduce`` (both wire modes,
  replicated output) and the FSDP backward reduce-scatter, with error
  decreasing in width;
* ``assign_mixed_widths`` puts more bits where norm^2-weighted expected
  variance is, at (or under) the mean-bits wire budget;
* ``resample_levels`` keeps endpoints/monotonicity and is identity at
  equal size.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.codec import (
    MixedWidthCodec,
    UniformCodec,
    WirePayload,
    assign_mixed_widths,
    codec_for_scheme,
    make_codec,
    resample_levels,
)
from repro.core.levels import num_levels, uniform_levels
from repro.core.packing import wire_bits_for
from repro.core.schemes import QuantScheme
from repro.dist import fsdp, sync
from repro.kernels import ops

KEY = jax.random.PRNGKey(11)
BS = 64


def _grad(d, scale=0.01, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (d,)) * scale


# ---------------------------------------------------------------------------
# round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", range(1, 9))
@pytest.mark.parametrize("norm_dtype", ["float32", "float16"])
def test_uniform_roundtrip_all_widths(bits, norm_dtype):
    codec = UniformCodec(num_levels=num_levels(bits), bucket_size=BS,
                         norm_type="l2", norm_dtype=norm_dtype)
    lv = uniform_levels(bits)
    flat = _grad(1000 + bits)  # ragged tail exercises padding
    plan = codec.plan(flat.shape[0])
    vb = codec.bucketize(flat, plan)
    pay = codec.encode(vb, lv, KEY, plan, use_pallas=False)
    assert pay.words.shape == (plan.code_words,)
    assert pay.norm_words.shape == (plan.norm_words,)

    vals = codec.decode(pay, lv, plan, use_pallas=False)
    # reference: same u draw, quantize, wire-rounded norms
    u = jax.random.uniform(KEY, vb.shape, jnp.float32)
    c, n = ops.quantize_op(vb, u, lv, norm_type="l2", use_pallas=False)
    if norm_dtype == "float16":
        n = n.astype(jnp.float16).astype(jnp.float32)
    ref = ops.dequantize_op(c, n, lv, use_pallas=False).reshape(-1)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref))


@pytest.mark.parametrize("widths", [(1, 3), (2, 4, 6), (2, 3, 4, 3),
                                    (8, 1), (5,)])
@pytest.mark.parametrize("norm_dtype", ["float32", "float16"])
def test_mixed_roundtrip_per_bucket_reference(widths, norm_dtype):
    codec = MixedWidthCodec(bucket_size=BS, norm_type="l2",
                            norm_dtype=norm_dtype, widths=widths)
    lv = uniform_levels(3)
    flat = _grad(16 * BS)
    plan = codec.plan(flat.shape[0])
    vb = codec.bucketize(flat, plan)
    pay = codec.encode(vb, lv, KEY, plan, use_pallas=False)
    assert pay.words.shape == (plan.code_words,)
    vals = np.asarray(codec.decode(pay, lv, plan,
                                   use_pallas=False)).reshape(plan.nb, BS)

    u = jax.random.uniform(KEY, vb.shape, jnp.float32)
    w = np.asarray(plan.widths)
    ref = np.zeros((plan.nb, BS), np.float32)
    for b in sorted(set(w.tolist())):
        idx = np.nonzero(w == b)[0]
        lvb = resample_levels(lv, num_levels(int(b)))
        c, n = ops.quantize_op(vb[idx], u[idx], lvb, norm_type="l2",
                               use_pallas=False)
        if norm_dtype == "float16":
            n = n.astype(jnp.float16).astype(jnp.float32)
        ref[idx] = np.asarray(
            ops.dequantize_op(c, n, lvb, use_pallas=False))
    np.testing.assert_array_equal(vals, ref)


def test_constant_width_mixed_equals_uniform_values():
    """Same grid, different layout machinery -> same decoded values."""
    scheme = QuantScheme(name="alq", bits=3, bucket_size=BS)
    lv = scheme.init_state().levels
    uc = codec_for_scheme(scheme)
    mc = MixedWidthCodec(bucket_size=BS, norm_type="l2", widths=(3,))
    flat = _grad(20 * BS)
    pu, pm = uc.plan(flat.shape[0]), mc.plan(flat.shape[0])
    vu = uc.decode(uc.encode(uc.bucketize(flat, pu), lv, KEY, pu,
                             use_pallas=False), lv, pu, use_pallas=False)
    vm = mc.decode(mc.encode(mc.bucketize(flat, pm), lv, KEY, pm,
                             use_pallas=False), lv, pm, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(vu), np.asarray(vm))
    assert pu.bits_per_coord == pm.bits_per_coord


# ---------------------------------------------------------------------------
# sharded payloads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", [
    UniformCodec(num_levels=8, bucket_size=BS, norm_type="l2"),
    MixedWidthCodec(bucket_size=BS, norm_type="l2", widths=(2, 4, 3)),
])
def test_sharded_diagonal_decode_matches_unsharded(codec):
    lv = uniform_levels(3)
    flat = _grad(32 * BS)
    M = 4
    plan = codec.plan(flat.shape[0], shards=M)
    vb = codec.bucketize(flat, plan)
    pay = codec.encode(vb, lv, KEY, plan, use_pallas=False)
    assert pay.words.shape == (M, plan.code_words)
    own = np.asarray(codec.decode(pay, lv, plan, shard=None,
                                  use_pallas=False)).reshape(-1)
    # static per-shard decode agrees with the diagonal
    for s in range(M):
        one = codec.decode(
            jax.tree.map(lambda a: a[s][None], pay), lv, plan, shard=s,
            use_pallas=False)
        np.testing.assert_array_equal(
            np.asarray(one)[0], own[s * plan.shard_n:(s + 1) * plan.shard_n])


def test_mixed_traced_shard_decode_under_vmap():
    """The lax.switch dispatch: each vmap lane decodes its own (static
    per-shard, different) layout from a traced rank."""
    mc = MixedWidthCodec(bucket_size=BS, norm_type="l2",
                         widths=(2, 5, 3, 4, 1, 6))
    lv = uniform_levels(3)
    flat = _grad(24 * BS)
    M = 4
    plan = mc.plan(flat.shape[0], shards=M)
    vb = mc.bucketize(flat, plan)
    pay = mc.encode(vb, lv, KEY, plan, use_pallas=False)
    ref = np.asarray(mc.decode(pay, lv, plan, shard=None,
                               use_pallas=False))

    def lane(w, nw):
        r = jax.lax.axis_index("w")
        out = mc.decode(WirePayload(w[None], nw[None]), lv, plan,
                        shard=r, use_pallas=False)
        return out[0]

    got = jax.vmap(lane, axis_name="w")(pay.words, pay.norm_words)
    np.testing.assert_array_equal(np.asarray(got), ref)


# ---------------------------------------------------------------------------
# end to end: allreduce + FSDP backward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["all_gather", "two_phase"])
def test_mixed_codec_quantized_allreduce(mode):
    M, D = 4, 6000
    scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
    state = scheme.init_state()
    grads = jax.random.normal(jax.random.PRNGKey(0), (M, D)) * 0.01
    exact = np.asarray(grads).mean(0)

    def err_at(widths):
        codec = MixedWidthCodec(bucket_size=256, norm_type="l2",
                                widths=widths)

        def worker(g):
            return sync.quantized_allreduce(
                g, scheme, state, KEY, axes=("w",), mode=mode,
                use_pallas=False, codec=codec)

        out, m = jax.vmap(worker, axis_name="w")(grads)
        out = np.asarray(out)
        assert (out == out[0]).all()  # replicated in every mode
        assert np.isfinite(float(m.comm_bits_per_coord[0]))
        return ((out[0] - exact) ** 2).sum()

    coarse, fine = err_at((2, 4)), err_at((7, 8))
    assert np.isfinite(coarse) and fine < coarse / 10


def test_mixed_codec_fsdp_backward():
    M, Lp = 4, 8192
    scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
    state = scheme.init_state()
    gf = jax.random.normal(jax.random.PRNGKey(3), (M, Lp)) * 0.01
    ref = np.asarray(gf).mean(0).reshape(M, -1)

    def rs_err(widths):
        codec = MixedWidthCodec(bucket_size=256, norm_type="l2",
                                widths=widths)
        rs = jax.vmap(
            lambda x: fsdp._quantized_reduce_scatter(
                x, state.levels, KEY, axes=("w",), codec=codec,
                use_pallas=False),
            axis_name="w")(gf)
        assert np.isfinite(np.asarray(rs)).all()
        return ((np.asarray(rs) - ref) ** 2).sum()

    assert rs_err((7, 8)) < rs_err((2, 4)) / 10


def test_make_gather_with_mixed_codec():
    """The full custom_vjp FSDP gather with a mixed-width codec, under
    real shard_map on fake devices (the custom_vjp backward composes
    with collective batching only under shard_map on this jax pin, so
    the harness matches tests/test_fsdp_quantized.py)."""
    import os
    import subprocess
    import sys

    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.codec import MixedWidthCodec
from repro.core.schemes import QuantScheme
from repro.dist import fsdp

M, Lp = 4, 4096
scheme = QuantScheme(name="alq", bits=8, bucket_size=256)
codec = MixedWidthCodec(bucket_size=256, norm_type="l2", widths=(7, 8))
gather = fsdp.make_gather(("w",), scheme, "quantized",
                          use_pallas=False, codec=codec)
lv = scheme.init_state().levels
key = jax.random.PRNGKey(11)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("w",))
shards = jax.random.normal(jax.random.PRNGKey(5), (Lp,))
target = np.asarray(
    jax.random.normal(jax.random.PRNGKey(6), (Lp,))) * 0.01

def worker_loss(s, t):
    full = gather(s, lv, key)
    return jnp.sum((full - t) ** 2)

f = jax.jit(jax.shard_map(
    lambda s, t: jax.grad(worker_loss)(s, t), mesh=mesh,
    in_specs=(P("w"), P()), out_specs=P("w"), check_vma=False))
grads = np.asarray(f(shards, jnp.asarray(target)))
exact = 2.0 * (np.asarray(shards) - target)
rel = np.abs(grads - exact).max() / np.abs(exact).max()
assert rel < 0.05, rel  # ~8-bit RS noise, mean over M workers
print("MIXED_GATHER_OK", rel)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"OUT:{proc.stdout}\nERR:{proc.stderr}"
    assert "MIXED_GATHER_OK" in proc.stdout


# ---------------------------------------------------------------------------
# width assignment + level resampling
# ---------------------------------------------------------------------------

def test_assignment_follows_norm_weighted_error():
    nb = 32
    mu = np.full(nb, 0.1)
    sig = np.full(nb, 0.05)
    norms = np.geomspace(0.01, 10.0, nb)
    wid = assign_mixed_widths(mu, sig, norms, uniform_levels(3),
                              mean_bits=3)
    assert len(wid) == nb
    # budget respected in WIRE bits
    budget = nb * wire_bits_for(num_levels(3))
    spent = sum(wire_bits_for(num_levels(b)) for b in wid)
    assert spent <= budget
    # monotone in bucket norm: the top-norm octile outranks the bottom
    assert np.mean(wid[-4:]) > np.mean(wid[:4])


def test_resample_levels_identity_endpoints_monotone():
    lv = jnp.asarray([0.0, 0.05, 0.2, 0.45, 0.6, 0.8, 0.9, 1.0])
    assert resample_levels(lv, 8) is lv
    for n in (2, 4, 16):
        out = np.asarray(resample_levels(lv, n))
        assert out.shape == (n,)
        assert out[0] == 0.0 and out[-1] == 1.0
        assert (np.diff(out) > 0).all()


@pytest.mark.parametrize("bits", [1, 2, 3, 7, 8])
def test_make_codec_default_mixed_pattern_is_budget_neutral(bits):
    """Including the range edges (1, 8), where the default cycle
    degenerates to the uniform width rather than overspending."""
    scheme = QuantScheme(name="alq", bits=bits, bucket_size=256)
    mc = make_codec(scheme, "mixed_width")
    uc = make_codec(scheme, "uniform")
    assert isinstance(mc, MixedWidthCodec)
    assert mc.nominal_bits_per_coord == pytest.approx(
        uc.nominal_bits_per_coord)
    with pytest.raises(ValueError):
        make_codec(scheme, "nope")
