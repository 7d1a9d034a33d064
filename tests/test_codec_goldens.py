"""Bit-exactness regression for the codec re-layering.

``tests/goldens/codec_goldens.npz`` was first generated by the
PRE-refactor implementation (hand-threaded ENCODE/pack/collective/
unpack/DECODE in ``dist/sync.py`` / ``dist/fsdp.py`` /
``sim/topology.py``) at a fixed seed.  jax 0.9.0 changed the numbers
underneath it: ``jax_threefry_partitionable`` became the default random
stream, and XLA's float32 sums round some bucket norms differently in
the last bit.  On the old stream the packed codes and byte counts were
still identical, so the file was re-recorded by
``tests/goldens/record_codec_goldens.py``.  These tests pin that the
codec-based paths produce payloads, aggregates, byte accounting, and
quantization-error metrics that are IDENTICAL, bit for bit:

* ``allreduce`` in both wire modes (all_gather / two_phase), fp32 and
  fp16 norm side-channels;
* ``param_server`` (8-bit downlink) and the per-hop re-quantizing
  ``ring``;
* worker-0's raw packed payload (words + norm words), unsharded and
  per-destination-shard;
* the FSDP quantized reduce-scatter (4 workers, 4 overlapped rounds).
"""
import os

import jax
import numpy as np
import pytest

from repro.core.codec import codec_for_scheme
from repro.core.schemes import QuantScheme
from repro.dist import fsdp
from repro.sim.topology import run_topology

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens",
                       "codec_goldens.npz")

M, D = 4, 6000


@pytest.fixture(scope="module")
def g():
    return np.load(GOLDENS)


@pytest.fixture(scope="module")
def setup():
    scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
    grads = jax.random.normal(jax.random.PRNGKey(0), (M, D)) * 0.01
    return scheme, scheme.init_state(), grads, jax.random.PRNGKey(7)


@pytest.mark.parametrize("topo,kw", [
    ("allreduce", dict(sync_mode="all_gather")),
    ("allreduce", dict(sync_mode="two_phase")),
    ("param_server", dict(server_bits=8)),
    ("ring", {}),
])
def test_topology_bit_exact_vs_prerefactor(g, setup, topo, kw):
    scheme, state, grads, key = setup
    r = run_topology(topo, grads, scheme, state, key, use_pallas=False,
                     **kw)
    name = topo + "_" + kw.get("sync_mode", "x")
    np.testing.assert_array_equal(np.asarray(r.aggregate),
                                  g[f"agg_{name}"])
    np.testing.assert_array_equal(np.asarray(r.sent_bytes),
                                  g[f"sent_{name}"])
    np.testing.assert_array_equal(np.asarray(r.quant_error),
                                  g[f"qerr_{name}"])


def test_two_phase_fp16_norms_bit_exact(g, setup):
    _, _, grads, key = setup
    s16 = QuantScheme(name="alq", bits=3, bucket_size=256,
                      norm_dtype="float16")
    r = run_topology("allreduce", grads, s16, s16.init_state(), key,
                     sync_mode="two_phase", use_pallas=False)
    np.testing.assert_array_equal(np.asarray(r.aggregate),
                                  g["agg_two_phase_f16"])


def test_payload_words_bit_exact(g, setup):
    """The packed bytes themselves — not just the decoded values —
    match what the pre-refactor packer emitted."""
    scheme, state, grads, key = setup
    codec = codec_for_scheme(scheme)
    k0 = jax.random.fold_in(key, 0)  # production per-worker fold

    plan = codec.plan(D)
    vb = codec.bucketize(grads[0].reshape(-1), plan)
    pay = codec.encode(vb, state.levels, k0, plan, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(pay.words), g["w0_words"])
    np.testing.assert_array_equal(np.asarray(pay.norm_words),
                                  g["w0_nwords"])

    plan4 = codec.plan(D, shards=M)
    vb4 = codec.bucketize(grads[0].reshape(-1), plan4)
    pay4 = codec.encode(vb4, state.levels, k0, plan4, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(pay4.words),
                                  g["w0_tp_words"])


def test_fsdp_reduce_scatter_bit_exact(g, setup):
    scheme, state, _, key = setup
    codec = codec_for_scheme(scheme)
    gf = jax.random.normal(jax.random.PRNGKey(3), (M, 8192)) * 0.01
    rs = jax.vmap(
        lambda x: fsdp._quantized_reduce_scatter(
            x, state.levels, key, axes=("w",), codec=codec,
            use_pallas=False),
        axis_name="w")(gf)
    np.testing.assert_array_equal(np.asarray(rs), g["fsdp_rs"])
