"""Cross-layer conformance suite for the entropy-coded wire
(docs/wire_format.md, "Entropy-coded payload family").

The ``EntropyCodec`` shares the quantize kernel and the key schedule
with ``UniformCodec`` — only the symbol *packaging* differs, and
entropy coding is lossless on symbols — so every decoded value must be
BIT-exact with the uncoded uniform path.  Pinned here:

* payload round trip against the uniform codec at every width 1..8 x
  {fp32, fp16} norms, unsharded and sharded (diagonal decode);
* every wire mode: ``run_topology`` allreduce (all_gather + two_phase),
  param_server, ring on 8 logical workers, plus the real shard_map
  paths (both allreduce modes + the FSDP chunked reduce-scatter) on 8
  fake devices in a subprocess;
* the forced-fallback path: a table built from adversarially skewed
  occupancies fed uniform-occupancy data overflows every bucket's
  capacity -> per-bucket fixed-width fallback (flag bit), still
  bit-exact, measured == capacity-ish;
* ``compress='ef'`` stacked on top decodes bit-exact against ef over
  the uniform codec (aggregates AND residual states); ``topk`` owns its
  SparseCodec, so an explicit entropy codec raises the config conflict;
* measured-volume accounting: ``measured_bits_per_coord`` == the plan
  for full-capacity payloads, strictly below the fixed-width plan for
  a fitted table on gaussian gradients, and consistent between the
  sharded and unsharded layouts of the same gradient;
* ``SyncMetrics`` / ``SyncMetricsLite`` / ``SchemeState`` metric-dtype
  pinning: every defaulted field is a float32 scalar, never a Python
  float, on every path including fp32 / no-update.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import make_algorithm
from repro.core.codec import (
    EntropyCodec,
    UniformCodec,
    codec_for_scheme,
    entropy_codec_from_gradient,
    entropy_wrap,
    make_codec,
)
from repro.core.levels import num_levels, uniform_levels
from repro.core.schemes import QuantScheme, SchemeState
from repro.dist import fsdp, sync
from repro.sim.topology import run_compressed, run_topology

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
KEY = jax.random.PRNGKey(11)
M, D, BS = 8, 6000, 256


def _scheme(bits=3, **kw):
    return QuantScheme(name="alq", bits=bits, bucket_size=BS, **kw)


def _grads(seed=0, m=M, d=D, scale=0.01):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, d)) * scale


def _fitted(scheme, flat, levels):
    return entropy_codec_from_gradient(flat, scheme, levels)


# ---------------------------------------------------------------------------
# codec-level conformance: decoded values == uniform codec, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", range(1, 9))
@pytest.mark.parametrize("norm_dtype", ["float32", "float16"])
def test_roundtrip_matches_uniform_all_widths(bits, norm_dtype):
    uc = UniformCodec(num_levels=num_levels(bits), bucket_size=64,
                      norm_type="l2", norm_dtype=norm_dtype)
    ec = entropy_wrap(uc)  # cold-start table
    lv = uniform_levels(bits)
    flat = _grads(seed=bits, m=1, d=1000 + bits)[0]
    pu, pe = uc.plan(flat.shape[0]), ec.plan(flat.shape[0])
    assert pe.variable and not pu.variable
    pay = ec.encode(ec.bucketize(flat, pe), lv, KEY, pe,
                    use_pallas=False)
    assert pay.words.shape == (pe.code_words,)
    ref = uc.decode(uc.encode(uc.bucketize(flat, pu), lv, KEY, pu,
                              use_pallas=False), lv, pu,
                    use_pallas=False)
    got = ec.decode(pay, lv, pe, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sharded_diagonal_decode_matches_uniform():
    scheme = _scheme()
    lv = scheme.init_state().levels
    uc = codec_for_scheme(scheme)
    ec = entropy_wrap(uc)
    flat = _grads(m=1, d=32 * BS)[0]
    pu = uc.plan(flat.shape[0], shards=4)
    pe = ec.plan(flat.shape[0], shards=4)
    payu = uc.encode(uc.bucketize(flat, pu), lv, KEY, pu,
                     use_pallas=False)
    paye = ec.encode(ec.bucketize(flat, pe), lv, KEY, pe,
                     use_pallas=False)
    assert paye.words.shape == (4, pe.code_words)
    ou = np.asarray(uc.decode(payu, lv, pu, shard=None,
                              use_pallas=False))
    oe = np.asarray(ec.decode(paye, lv, pe, shard=None,
                              use_pallas=False))
    np.testing.assert_array_equal(ou, oe)
    # static per-shard decode agrees with the diagonal (every segment
    # shares one static layout; no lax.switch needed)
    for s in range(4):
        one = ec.decode(jax.tree.map(lambda a: a[s][None], paye), lv,
                        pe, shard=s, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(one)[0], oe[s])


# ---------------------------------------------------------------------------
# wire-mode conformance on 8 logical workers (vmap named axes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo,kw", [
    ("allreduce", dict(sync_mode="all_gather")),
    ("allreduce", dict(sync_mode="two_phase")),
    ("param_server", dict(server_bits=8)),
    ("param_server", dict(server_bits=None)),
    ("ring", {}),
])
def test_topology_conformance_vs_uniform(topo, kw):
    scheme = _scheme()
    state = scheme.init_state()
    grads = _grads()
    ec = _fitted(scheme, grads[0], state.levels)
    r_u = run_topology(topo, grads, scheme, state, KEY,
                       use_pallas=False, **kw)
    r_e = run_topology(topo, grads, scheme, state, KEY, codec=ec,
                       use_pallas=False, **kw)
    np.testing.assert_array_equal(np.asarray(r_u.aggregate),
                                  np.asarray(r_e.aggregate))
    np.testing.assert_array_equal(np.asarray(r_u.quant_error),
                                  np.asarray(r_e.quant_error))
    # the entropy wire never bills MORE than the uniform plan shipped
    # (headers cost 32/bucket_size; the coded runs more than pay it
    # back on gaussian gradients), except the capacity-billed ring
    if topo != "ring":
        assert (np.asarray(r_e.wire_bits_per_coord)
                <= np.asarray(r_u.wire_bits_per_coord) + 1e-5).all(), (
            r_e.wire_bits_per_coord, r_u.wire_bits_per_coord)


def test_fsdp_reduce_scatter_conformance():
    """The FSDP chunked quantized reduce-scatter carries coded chunks
    (headers + regions ride the generic payload all-to-all) and decodes
    bit-exact against the uniform codec."""
    scheme = _scheme()
    state = scheme.init_state()
    gf = _grads(seed=3, m=4, d=8192)

    def rs(codec):
        return np.asarray(jax.vmap(
            lambda x: fsdp._quantized_reduce_scatter(
                x, state.levels, KEY, axes=("w",), codec=codec,
                use_pallas=False),
            axis_name="w")(gf))

    uc = codec_for_scheme(scheme)
    ec = _fitted(scheme, gf[0], state.levels)
    assert ec.chunkable  # the k-round overlap re-plans sub-ranges
    np.testing.assert_array_equal(rs(uc), rs(ec))


def test_shard_map_conformance_8_fake_devices():
    """Real mesh collectives: both allreduce wire modes and the FSDP
    reduce-scatter under shard_map on 8 fake devices, entropy vs
    uniform bit-exact."""
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.codec import codec_for_scheme, entropy_codec_from_gradient
from repro.core.schemes import QuantScheme
from repro.dist import fsdp, sync

M, D = 8, 4096
scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
state = scheme.init_state()
from repro.launch.mesh import make_mesh
mesh = make_mesh((M,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (M, D)) * 0.01
key = jax.random.PRNGKey(7)
uc = codec_for_scheme(scheme)
ec = entropy_codec_from_gradient(np.asarray(g[0]), scheme, state.levels)

for mode in ("all_gather", "two_phase"):
    def f(gl, codec):
        out, m = sync.quantized_allreduce(
            gl.reshape(-1), scheme, state, key, axes=("data",),
            mode=mode, use_pallas=False, codec=codec)
        return out, m.comm_bits_per_coord
    outs = {}
    for name, codec in (("uniform", uc), ("entropy", ec)):
        smf = jax.jit(jax.shard_map(
            lambda gl: f(gl, codec), mesh=mesh,
            in_specs=P("data"), out_specs=(P(), P()), check_vma=False))
        outs[name] = smf(g)
    assert (np.asarray(outs["uniform"][0])
            == np.asarray(outs["entropy"][0])).all(), mode
    assert (float(outs["entropy"][1])
            <= float(outs["uniform"][1]) + 1e-5), mode

def rs(codec):
    smf = jax.jit(jax.shard_map(
        lambda x: fsdp._quantized_reduce_scatter(
            x.reshape(-1), state.levels, key, axes=("data",),
            codec=codec, use_pallas=False),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    return np.asarray(smf(g.reshape(M, -1)))
assert (rs(uc) == rs(ec)).all()
print("ENTROPY_CONFORMANCE_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"OUT:{proc.stdout}\nERR:{proc.stderr}"
    assert "ENTROPY_CONFORMANCE_OK" in proc.stdout


# ---------------------------------------------------------------------------
# forced fallback: adversarial occupancies overflow the coded capacity
# ---------------------------------------------------------------------------

def test_forced_fallback_is_bit_exact_and_flagged():
    scheme = QuantScheme(name="qsgdinf", bits=3, bucket_size=BS)
    state = scheme.init_state()
    uc = codec_for_scheme(scheme)
    # table fit to "everything is zero" => long codes for every nonzero
    # symbol; uniform-occupancy data (large magnitudes hit all levels)
    # then overflows every bucket's fixed-width capacity
    skew = np.zeros(scheme.num_levels)
    skew[0] = 1.0
    ec = entropy_wrap(uc, skew)
    flat = jax.random.uniform(jax.random.PRNGKey(1), (BS * 16,)) * 2 - 1
    lv = state.levels
    pe, pu = ec.plan(flat.shape[0]), uc.plan(flat.shape[0])
    pay = ec.encode(ec.bucketize(flat, pe), lv, KEY, pe,
                    use_pallas=False)
    flags = np.asarray(pay.words[:pe.shard_nb]) >> 31
    assert flags.all(), "adversarial table must force every bucket back"
    ref = uc.decode(uc.encode(uc.bucketize(flat, pu), lv, KEY, pu,
                              use_pallas=False), lv, pu,
                    use_pallas=False)
    got = ec.decode(pay, lv, pe, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # fallback ships capacity + headers: measured == the entropy plan's
    # own worst case, slightly ABOVE the uniform plan (the header tax)
    mb = float(ec.measured_bits_per_coord(pay, pe))
    assert mb == pytest.approx(pe.bits_per_coord, rel=1e-6)
    assert mb >= pu.bits_per_coord


def test_fitted_table_measures_below_fixed_width():
    scheme = _scheme()
    state = scheme.init_state()
    flat = _grads(m=1, d=64 * BS)[0]
    ec = _fitted(scheme, flat, state.levels)
    uc = codec_for_scheme(scheme)
    pe, pu = ec.plan(flat.shape[0]), uc.plan(flat.shape[0])
    pay = ec.encode(ec.bucketize(flat, pe), state.levels, KEY, pe,
                    use_pallas=False)
    mb = float(ec.measured_bits_per_coord(pay, pe))
    assert mb < pu.bits_per_coord, (mb, pu.bits_per_coord)
    # sharded layout of the same gradient bills (almost) the same bytes
    # (per-segment norm-word alignment only)
    pe4 = ec.plan(flat.shape[0], shards=4)
    pay4 = ec.encode(ec.bucketize(flat, pe4), state.levels, KEY, pe4,
                     use_pallas=False)
    mb4 = float(ec.measured_bits_per_coord(pay4, pe4))
    assert mb4 == pytest.approx(mb, rel=0.02)


# ---------------------------------------------------------------------------
# compress algorithms stacked on the entropy codec
# ---------------------------------------------------------------------------

def test_ef_stacked_on_entropy_codec_bit_exact():
    scheme = QuantScheme(name="qsgdinf", bits=2, bucket_size=BS)
    state = scheme.init_state()
    grads = _grads(m=4)
    ec = _fitted(scheme, grads[0], state.levels)

    def run(codec, comp_state):
        algo = make_algorithm("ef", scheme, codec=codec)
        return run_compressed("allreduce", grads, scheme, state, algo,
                              comp_state, KEY, use_pallas=False)

    cs0 = jax.tree.map(
        lambda a: jnp.stack([a] * 4),
        make_algorithm("ef", scheme).init_state(D))
    ru, su = run(codec_for_scheme(scheme), cs0)
    re, se = run(ec, cs0)
    np.testing.assert_array_equal(np.asarray(ru.aggregate),
                                  np.asarray(re.aggregate))
    np.testing.assert_array_equal(np.asarray(su.residual),
                                  np.asarray(se.residual))


def test_topk_rejects_entropy_codec():
    """topk owns its SparseCodec; stacking it on an explicit entropy
    codec is a config conflict, pinned as a raise (not a silent
    discard)."""
    scheme = _scheme()
    ec = entropy_wrap(codec_for_scheme(scheme))
    with pytest.raises(ValueError, match="SparseCodec"):
        make_algorithm("topk", scheme, codec=ec)


def test_entropy_wrap_rejects_non_uniform_bases():
    from repro.core.codec import MixedWidthCodec
    with pytest.raises(ValueError, match="uniform"):
        entropy_wrap(MixedWidthCodec(bucket_size=BS, widths=(2, 4)))
    scheme = _scheme()
    with pytest.raises(ValueError, match="uniform"):
        make_codec(scheme, "entropy:mixed_width")
    assert isinstance(make_codec(scheme, "entropy"), EntropyCodec)
    assert isinstance(make_codec(scheme, "entropy:uniform"),
                      EntropyCodec)


def test_bad_table_raises():
    with pytest.raises(ValueError, match="signed"):
        EntropyCodec(num_levels=8, bucket_size=BS,
                     huff_lengths=(3,), huff_codes=(0,))


# ---------------------------------------------------------------------------
# metric-dtype pinning: no Python floats leak through SyncMetrics
# ---------------------------------------------------------------------------

def _assert_f32_scalar(name, x):
    assert not isinstance(x, (float, int)), (
        f"{name} leaked a Python scalar: {x!r}")
    assert jnp.asarray(x).dtype == jnp.float32, (name, x)


@pytest.mark.parametrize("mode", ["fp32", "all_gather", "two_phase"])
def test_sync_metrics_fields_are_float32(mode):
    scheme = _scheme() if mode != "fp32" else QuantScheme(name="fp32")
    state = scheme.init_state()
    flat = _grads(m=1, d=4 * BS)[0]
    _, m = sync.quantized_allreduce(flat, scheme, state, KEY, axes=(),
                                    mode=mode, use_pallas=False)
    for name, val in zip(m._fields, m):
        _assert_f32_scalar(name, val)


def test_metric_defaults_are_float32_scalars():
    """The no-update / stateless construction paths: defaulted
    NamedTuple fields must already be float32 scalars."""
    from repro.train.train_step import SyncMetricsLite
    m = sync.SyncMetrics(jnp.float32(1.0), jnp.float32(0.0),
                         jnp.float32(1.0), jnp.float32(0.0))
    for name in ("entropy_bits_per_coord", "residual_norm",
                 "kept_fraction"):
        _assert_f32_scalar(name, getattr(m, name))
    lite = SyncMetricsLite(jnp.float32(1.0), jnp.float32(0.0),
                           jnp.float32(1.0), jnp.float32(0.0),
                           jnp.float32(0.0))
    for name in ("residual_norm", "kept_fraction"):
        _assert_f32_scalar(name, getattr(lite, name))
    # SchemeState constructed positionally (the benchmark harness path)
    s = SchemeState(uniform_levels(3), jnp.float32(0.5),
                    jnp.asarray(0, jnp.int32))
    _assert_f32_scalar("entropy_bits", s.entropy_bits)
