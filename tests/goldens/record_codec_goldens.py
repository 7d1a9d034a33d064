"""Record ``codec_goldens.npz``, the bit-exact pins of
``tests/test_codec_goldens.py`` and ``tests/test_compress.py``.

Run only when the installed jax changes the numbers themselves (its
random stream or its float reductions), never to absorb a change of the
program.  From the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/goldens/record_codec_goldens.py

The inputs are the tests' own: M=4 workers, D=6000 coordinates of
N(0, 0.01^2) gradients from PRNGKey(0), 3-bit ALQ at bucket 256, wire
key PRNGKey(7).
"""
import os

import jax
import numpy as np

from repro.core.codec import codec_for_scheme
from repro.core.schemes import QuantScheme
from repro.dist import fsdp
from repro.sim.topology import run_topology

M, D = 4, 6000
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "codec_goldens.npz")


def record() -> dict:
    scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
    state = scheme.init_state()
    grads = jax.random.normal(jax.random.PRNGKey(0), (M, D)) * 0.01
    key = jax.random.PRNGKey(7)
    out = {}
    for topo, kw in [("allreduce", dict(sync_mode="all_gather")),
                     ("allreduce", dict(sync_mode="two_phase")),
                     ("param_server", dict(server_bits=8)),
                     ("ring", {})]:
        r = run_topology(topo, grads, scheme, state, key, use_pallas=False,
                         **kw)
        name = topo + "_" + kw.get("sync_mode", "x")
        out[f"agg_{name}"] = r.aggregate
        out[f"sent_{name}"] = r.sent_bytes
        out[f"qerr_{name}"] = r.quant_error

    s16 = QuantScheme(name="alq", bits=3, bucket_size=256,
                      norm_dtype="float16")
    out["agg_two_phase_f16"] = run_topology(
        "allreduce", grads, s16, s16.init_state(), key,
        sync_mode="two_phase", use_pallas=False).aggregate

    codec = codec_for_scheme(scheme)
    k0 = jax.random.fold_in(key, 0)
    plan = codec.plan(D)
    pay = codec.encode(codec.bucketize(grads[0], plan), state.levels, k0,
                       plan, use_pallas=False)
    out["w0_words"], out["w0_nwords"] = pay.words, pay.norm_words
    plan4 = codec.plan(D, shards=M)
    out["w0_tp_words"] = codec.encode(
        codec.bucketize(grads[0], plan4), state.levels, k0, plan4,
        use_pallas=False).words

    gf = jax.random.normal(jax.random.PRNGKey(3), (M, 8192)) * 0.01
    out["fsdp_rs"] = jax.vmap(
        lambda x: fsdp._quantized_reduce_scatter(
            x, state.levels, key, axes=("w",), codec=codec,
            use_pallas=False),
        axis_name="w")(gf)
    return {k: np.asarray(v) for k, v in out.items()}


if __name__ == "__main__":
    arrays = record()
    np.savez(OUT, **arrays)
    print(f"wrote {len(arrays)} arrays to {OUT}")
