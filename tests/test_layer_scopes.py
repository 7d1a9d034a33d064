"""The train step's layer scopes reach the compiled step, and the
launcher's host spans reach a profile.

Each wire's step (the reduced Qwen3, jnp reference kernels) is compiled
and its text read with the benchmark's own rule (``bench/scopes.py``):
every scope the wire runs names at least one instruction, and no scope
outside ``LAYER_SCOPES`` appears.  The two-device ``two_phase`` step
runs in a subprocess with two fake CPU devices (``XLA_FLAGS`` must be
set before jax is imported); only there does the collective survive
compilation for certain.
"""
import glob
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes  # noqa: E402
from repro.train.train_step import LAYER_SCOPES  # noqa: E402

ARGS = ["--arch", "qwen3-0.6b", "--smoke", "--no-use-pallas", "--batch",
        "2", "--seq", "16", "--data", "uniform", "--update-at", "0"]
# op_name segments JAX itself writes: control flow, remat, calls
JAX_SEGMENT = re.compile(r"^(while|body|cond|closed_call|checkpoint|"
                         r"rematted_computation|shard_map|branch_\d+_fun)$")
WIRES = {
    "all_gather": (["--sync", "all_gather", "--scheme", "alq"], 1,
                   {"fwd_bwd", "ravel", "level_update", "encode", "decode",
                    "optimizer", "step_metrics"}),
    "fp32": (["--sync", "fp32", "--scheme", "fp32"], 1,
             {"fwd_bwd", "ravel", "optimizer", "step_metrics"}),
    "two_phase": (["--sync", "two_phase", "--scheme", "alq"], 2,
                  set(LAYER_SCOPES)),
}

COMPILE = r"""
import sys
import jax
from repro.launch import train
from repro.launch.mesh import make_local_mesh
args = train.parse_args(sys.argv[2:])
tr = train.build(args, make_local_mesh(devices=jax.devices()))
with jax.set_mesh(tr.mesh):
    state = jax.eval_shape(tr.init)
    batch = jax.eval_shape(lambda: tr.pipe.batch(0))
    text = tr.step.lower(state, batch).compile().as_text()
with open(sys.argv[1], "w") as f:
    f.write(text)
"""


def compiled_text(argv, devices, tmp_path):
    if devices == 1:
        import jax
        from repro.launch import train
        from repro.launch.mesh import make_local_mesh
        # JAX keeps traced and lowered functions in process-wide caches,
        # and a lowering can keep the op_names of the trace that made it:
        # after the simulator's fault scenario, this step's ops read
        # jit(_threefry_fold_in)/quantized_allreduce/...  Compile from
        # empty caches, as a process of its own would.
        jax.clear_caches()
        tr = train.build(train.parse_args(argv),
                         make_local_mesh(devices=jax.devices()[:1]))
        with jax.set_mesh(tr.mesh):
            state = jax.eval_shape(tr.init)
            batch = jax.eval_shape(lambda: tr.pipe.batch(0))
            return tr.step.lower(state, batch).compile().as_text()
    out = tmp_path / "step.hlo"
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", COMPILE, str(out)] + argv,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return out.read_text()


def scope_like_segments(text):
    """The plain-name segments of every op_name but its last (the
    primitive), less those JAX writes itself."""
    out = set()
    for op in re.findall(r'op_name="([^"]*)"', text):
        for seg in op.split("/")[:-1]:
            if (re.fullmatch(r"[A-Za-z_]\w*", seg)
                    and not JAX_SEGMENT.match(seg)):
                out.add(seg)
    return out


def test_program_and_benchmark_name_the_same_scopes():
    assert LAYER_SCOPES == scopes.LAYERS
    assert len(set(LAYER_SCOPES)) == len(LAYER_SCOPES)


@pytest.mark.parametrize("wire", sorted(WIRES))
def test_each_layer_scope_reaches_the_compiled_step(wire, tmp_path):
    extra, devices, want = WIRES[wire]
    text = compiled_text(ARGS + extra, devices, tmp_path)
    module = scopes.parse(text)
    found = {v for v in module.layer.values() if v is not None}
    assert want <= found, sorted(want - found)
    if devices == 1:
        # on one device the compiler may drop the collective
        found.discard("collective")
    assert found <= want, sorted(found - want)
    assert scope_like_segments(text) <= set(LAYER_SCOPES)


def test_launcher_host_spans_reach_a_profile(tmp_path):
    import jax
    from jax.profiler import ProfileData
    from repro.launch import train
    from repro.launch.mesh import make_local_mesh

    args = train.parse_args(ARGS + ["--sync", "fp32", "--scheme", "fp32",
                                    "--steps", "3"])
    with jax.profiler.trace(str(tmp_path)):
        res = train.run(args, make_local_mesh(devices=jax.devices()[:1]))
    assert len(res.metrics) == 3
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    counts = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    counts[e.name] = counts.get(e.name, 0) + 1
    # the next batch is built after each step but the last
    assert counts.get("dispatch") == 3 and counts.get("wait") == 3
    assert counts.get("batch") == 2
    assert counts.get("train") == 3
