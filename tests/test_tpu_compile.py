"""Compile the Pallas kernels and the wire of the quantization path
for a TPU v5e.

Nothing runs: the TPU compiler installed with jax compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the tiling, too much VMEM).  The interpret-mode tests
in test_kernels.py cannot see either.  Each compile takes a second or
two.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests and only the worker given this
file loads the TPU library.  Its compiler logs are turned off: they
would otherwise go to a fixed directory outside the checkout.
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import code_dtype, packing, uniform_levels
from repro.core.codec import WirePayload, codec_for_scheme
from repro.core.schemes import QuantScheme
from repro.kernels import ops

# bucket counts of a real flat gradient: 586 = a 0.6M-coordinate share
# at bucket 1024 (not a multiple of 8), 1000 and 63 for ragged last
# tiles at both bucket sizes
SHAPES = [(586, 1024), (1000, 1024), (63, 8192), (586, 8192)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bits", [3, 8])
@pytest.mark.parametrize("nb,bs", SHAPES)
def test_quantize_compiles(one_chip, nb, bs, bits):
    nlev = uniform_levels(bits).shape[0]
    v = jax.ShapeDtypeStruct((nb, bs), jnp.float32, sharding=one_chip)
    lv = jax.ShapeDtypeStruct((nlev,), jnp.float32, sharding=one_chip)
    _compile(lambda v, u, lv: ops.quantize_op(v, u, lv), v, v, lv)


@pytest.mark.parametrize("bits", [3, 8])
@pytest.mark.parametrize("nb,bs", SHAPES)
def test_dequantize_compiles(one_chip, nb, bs, bits):
    nlev = uniform_levels(bits).shape[0]
    codes = jax.ShapeDtypeStruct((nb, bs), code_dtype(nlev),
                                 sharding=one_chip)
    norms = jax.ShapeDtypeStruct((nb,), jnp.float32, sharding=one_chip)
    lv = jax.ShapeDtypeStruct((nlev,), jnp.float32, sharding=one_chip)
    _compile(lambda c, n, lv: ops.dequantize_op(c, n, lv), codes, norms, lv)


@pytest.mark.parametrize("nb,bs", SHAPES)
def test_bucket_stats_compiles(one_chip, nb, bs):
    v = jax.ShapeDtypeStruct((nb, bs), jnp.float32, sharding=one_chip)
    _compile(lambda v: ops.bucket_stats_op(v), v)


def _compile_s(fn, *args):
    t0 = time.perf_counter()
    jax.jit(fn).lower(*args).compile()
    return time.perf_counter() - t0


def test_pack_compiles_in_seconds(one_chip):
    """The pack's scatters are told their word indices are sorted; at a
    width that does not divide 32 the compiler otherwise takes about
    35 s at this length, 2 s with it."""
    codes = jax.ShapeDtypeStruct((112 << 20,), jnp.int16, sharding=one_chip)
    assert _compile_s(lambda c: packing.pack_signed(c, 256), codes) < 15


def test_gathered_decode_compiles_in_seconds(one_chip):
    """Four gathered 16M-coordinate streams decode with per-row reshapes;
    reshaping all rows at once between (M, n) and buckets took the
    compiler about 100 s."""
    scheme = QuantScheme(name="alq", bits=3, bucket_size=1024)
    codec = codec_for_scheme(scheme)
    plan = codec.plan(16 << 20)
    payload = WirePayload(
        jax.ShapeDtypeStruct((4, plan.code_words), jnp.uint32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((4, plan.norm_words), jnp.uint32,
                             sharding=one_chip))
    levels = scheme.init_state().levels
    assert _compile_s(lambda p: codec.decode(p, levels, plan).mean(0),
                      payload) < 30
