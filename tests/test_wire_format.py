"""Wire-format coverage (docs/wire_format.md).

* pack/unpack round-trips for every wire width 1..8 at non-word-aligned
  lengths — exercising both the word-boundary spill path (bits not
  dividing 32) and the ``off == 0`` masked-shift path (bits dividing 32);
* packed size is exactly ceil(n*b/32) words;
* ``quantized_allreduce(all_gather)`` on the 8-fake-device mesh equals an
  unpacked (codes-never-packed) reference BIT-exactly — the wire really
  carries packed words, and packing is lossless end to end.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# word-boundary spill (33: symbol straddles words for b not dividing 32),
# off==0 masked-shift (exact multiples of 32/b), and ragged tails
LENGTHS = [1, 5, 31, 32, 33, 37, 64, 65, 255, 1000]


@pytest.mark.parametrize("bits", range(1, 9))
@pytest.mark.parametrize("n", LENGTHS)
def test_pack_unpack_roundtrip_all_wire_widths(bits, n):
    rng = np.random.default_rng(bits * 10007 + n)
    vals = rng.integers(0, 2 ** bits, size=n, dtype=np.int64)
    packed = packing.pack(jnp.asarray(vals, jnp.int32), bits)
    assert packed.dtype == jnp.uint32
    assert packed.shape[0] == packing.packed_words(n, bits) == -(-n * bits // 32)
    back = packing.unpack(packed, n, bits)
    np.testing.assert_array_equal(np.asarray(back), vals)


@pytest.mark.parametrize("num_levels", [2, 8, 16, 128, 256])
def test_signed_roundtrip_at_scheme_level_counts(num_levels):
    rng = np.random.default_rng(num_levels)
    n = 999  # deliberately non-word-aligned
    codes = rng.integers(-(num_levels - 1), num_levels, size=n)
    packed = packing.pack_signed(jnp.asarray(codes, jnp.int32), num_levels)
    back = packing.unpack_signed(packed, n, num_levels)
    np.testing.assert_array_equal(np.asarray(back), codes)


def test_allreduce_matches_unpacked_reference_bit_exactly():
    body = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import packing
from repro.core.schemes import QuantScheme
from repro.dist import sync
from repro.kernels import ops

scheme = QuantScheme(name="alq", bits=3, bucket_size=256)
state = scheme.init_state()
M = 8
d = 2048  # per-worker length; 8 buckets per worker
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (M, d)) * 0.01
key = jax.random.PRNGKey(7)

def f(gl):
    out, _ = sync.quantized_allreduce(gl.reshape(-1), scheme, state, key,
                                      axes=("pod", "data"))
    return out
smf = jax.jit(jax.shard_map(f, mesh=mesh,
    in_specs=P(("pod", "data")), out_specs=P(), check_vma=False))
packed_out = np.asarray(smf(g))

# unpacked reference: same encode (same folded keys/uniforms), but the
# codes are decoded directly — no pack/all_gather/unpack in the loop
codes_all, norms_all = [], []
for r in range(M):
    vb = g[r].reshape(-1, scheme.bucket_size)
    u = jax.random.uniform(jax.random.fold_in(key, r), vb.shape, jnp.float32)
    codes, norms = ops.quantize_op(vb, u, state.levels,
                                   norm_type=scheme.norm_type)
    # packing must be lossless on the actual code stream too
    w = packing.pack_signed(codes, scheme.num_levels)
    back = packing.unpack_signed(w, codes.size, scheme.num_levels)
    assert (np.asarray(back).reshape(codes.shape)
            == np.asarray(codes, np.int32)).all()
    codes_all.append(codes)
    norms_all.append(norms)
# each worker decoded to its own row, then the mean over the stacked
# rows, as the collective's decode hands them to the mean
per_worker = jnp.stack([ops.dequantize_op(c, n, state.levels).reshape(-1)
                        for c, n in zip(codes_all, norms_all)])
ref = np.asarray(jax.jit(lambda x: x.mean(0))(per_worker))
assert (packed_out == ref).all(), np.abs(packed_out - ref).max()
print("WIRE_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", body], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"OUT:{proc.stdout}\nERR:{proc.stderr}"
    assert "WIRE_OK" in proc.stdout
