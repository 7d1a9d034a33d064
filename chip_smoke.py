#!/usr/bin/env python3
"""Smoke run of adaptively quantized data-parallel training on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the data-parallel path

One chip: trains Qwen3-0.6B (published widths, depth cut to fit the
chip's memory) for a few steps through ``repro.launch.train`` with 3-bit
ALQ, the all_gather wire and the Pallas kernels, with one level update
inside the run.  It then checks the kernels against ``kernels/ref.py``
on the first step's flat gradient at buckets 1024 and 8192, the wire's
aggregate of that gradient against the variance of the scheme's
rounding, and step 0's loss against the same step with fp32 sync.

Four chips: DP = 4 steps on the quantized two_phase wire against fp32
psum steps on the same batches, and the two_phase aggregate of the four
workers' first gradients against the variance of its rounding.  The
all_gather wire is not run there: its (M, n) decode does not fit a
chip's memory at these widths.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Any other platform than a TPU
exits non-zero before a phase runs.  Sets no compilation-cache directory
of its own where ``JAX_COMPILATION_CACHE_DIR`` is set, and keeps the TPU
compiler's logs off unless ``TPU_LOG_DIR`` names a directory for them.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# read when jax loads the TPU runtime; unset, its logs go outside the
# checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.flatten_util import ravel_pytree  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.codec import codec_for_scheme  # noqa: E402
from repro.core.levels import uniform_levels  # noqa: E402
from repro.core.quantize import NORM_L2, NORM_LINF, pad_to_buckets  # noqa: E402
from repro.dist.sync import TWO_PHASE_BITS, quantized_allreduce  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402

ARCH = "qwen3-0.6b"
# depth: 9 of Qwen3-0.6B's 28 layers, run on one v5e and on four; widths
# are never cut.  The full depth does not fit one chip's HBM, and the
# compiler's HBM check is not monotonic in depth (for one v5e it accepts
# the all_gather step at 9 to 11 and at 16 layers, and refuses 13)
LAYERS = 9
# the launcher arguments of both paths
TRAIN_ARGS = ["--arch", ARCH, "--scheme", "alq", "--bits", "3",
              "--bucket", "1024", "--steps", "5", "--batch", "4",
              "--seq", "128", "--data", "uniform", "--update-at", "2",
              "--use-pallas"]
BUCKETS = (1024, 8192)
# DP = 4 steps per wire: the second shows a step after a quantized
# update
DP4_STEPS = 2
# bound, in standard deviations, on the sync check's two statistics
Z_MAX = 6.0
# buckets per block of the sync check's sums
CHECK_BLOCK = 4096


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def train_run(train_args, mesh, *extra):
    args = train.parse_args([*train_args, *extra])
    return args, train.run(args, mesh)


def report_run(name: str, args, res) -> dict:
    """Print a run's per-step lines and check what every run must show:
    finite losses and the wire plan's bits/coord."""
    d = sum(int(x.size) for x in jax.tree.leaves(res.state.params))
    scheme = res.trainer.tcfg.scheme
    for t, m in enumerate(res.metrics):
        print(f"{name} step {t} loss={m['loss']!r} "
              f"bits/coord={m['comm_bits_per_coord']!r} "
              f"reduce={m['reduce_bits_per_coord']!r} "
              f"ms={res.step_s[t] * 1e3:.1f}", flush=True)
        check(np.isfinite(m["loss"]), f"{name}: non-finite loss at step {t}")
    # all_gather ships one hop (comm); two_phase's reduce hop carries the
    # scheme's plan and its broadcast hop the aggregate's re-quantization
    key = ("reduce_bits_per_coord" if args.sync == "two_phase"
           else "comm_bits_per_coord")
    if args.sync == "fp32":
        want = 32.0
    else:
        shards = res.trainer.mesh.size if args.sync == "two_phase" else 1
        want = float(np.float32(
            codec_for_scheme(scheme).plan(d, shards=shards).bits_per_coord))
    got = [m[key] for m in res.metrics]
    check(all(g == want for g in got),
          f"{name}: {key} {got!r} != the plan's {want!r}")
    steady = statistics.median(res.step_s[1:] or res.step_s)
    kernels = "tpu_custom_call" in res.compiled.as_text()
    mem = res.compiled.memory_analysis()
    levels0 = np.asarray(scheme.init_state().levels)
    levels = np.asarray(res.state.scheme_state.levels)
    updated = not np.array_equal(levels0, levels)
    print(f"{name}: params={d} compile_s={res.compile_s!r} "
          f"steady_ms_per_step={steady * 1e3!r} "
          f"step_argument_bytes={mem.argument_size_in_bytes} "
          f"step_temp_bytes={mem.temp_size_in_bytes} "
          f"tpu_custom_call={kernels} levels_updated={updated} "
          f"levels={levels.round(4).tolist()}", flush=True)
    return {"kernels": kernels, "updated": updated, "levels": levels,
            "losses": [m["loss"] for m in res.metrics]}


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def data_axes(tr) -> tuple:
    return tuple(a for a in tr.mesh.axis_names if a != "model")


def step0_gradients(tr) -> jnp.ndarray:
    """Each worker's flat gradient at step 0 (fresh parameters from the
    launcher's seed, the launcher's first batch), as the rows of an
    (M, d) array sharded over the data axes."""
    axes = data_axes(tr)
    params = jax.jit(lambda: tr.init().params,
                     out_shardings=tr.state_shardings.params)()
    batch = jax.device_put(tr.pipe.batch(0), tr.batch_sharding)

    def grad(p, b):
        return ravel_pytree(jax.grad(tr.model.loss)(p, b))[0][None]

    f = jax.jit(jax.shard_map(
        grad, mesh=tr.mesh,
        in_specs=(tr.model.param_specs(), P(axes)),
        out_specs=P(axes), check_vma=False))
    with jax.set_mesh(tr.mesh):
        return f(params, batch)


def rounding_moments(v, levels, norm_type: str):
    """E[e^2] and E[e^4], coordinate by coordinate, of the error
    e = Q(v) - v of unbiased stochastic rounding of the buckets ``v``
    (rows) onto ``levels`` scaled by each bucket's norm.  A coordinate
    at distance b above its lower grid point and a below its upper one
    rounds up with probability b / (a + b): E[e^2] = ab and
    E[e^4] = ab (a^2 - ab + b^2)."""
    v = jnp.abs(v)
    norms = (jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))
             if norm_type == NORM_L2 else jnp.max(v, axis=1, keepdims=True))
    r = v / jnp.where(norms > 0, norms, 1.0)
    # the grid interval of r, counted level by level (levels[0] = 0 and
    # levels[-1] = 1 bound every r)
    tau = jnp.zeros(r.shape, jnp.int32)
    for j in range(1, levels.shape[0] - 1):
        tau = tau + (r >= levels[j])
    a = levels[tau + 1] * norms - v
    b = v - levels[tau] * norms
    s2 = a * b
    return s2, s2 * (a * a - a * b + b * b)


def blocks(x, bucket: int):
    """(d,) -> (k, CHECK_BLOCK, bucket), zero-padded: whole buckets, in
    blocks the check's sums visit one at a time so that their
    temporaries stay a block in size."""
    size = CHECK_BLOCK * bucket
    k = -(-x.shape[0] // size)
    return jnp.pad(x, (0, k * size - x.shape[0])).reshape(
        k, CHECK_BLOCK, bucket)


def aggregate(tr, sync: str):
    """The jitted ``sync`` wire: (M, d) rows of the workers' gradients
    in, each worker's copy of their aggregate out, as (M, d) rows."""
    scheme = tr.tcfg.scheme
    axes = data_axes(tr)

    def wire(g):
        out, _ = quantized_allreduce(
            g[0], scheme, scheme.init_state(), jax.random.PRNGKey(1),
            axes=axes, mode=sync, use_pallas=tr.tcfg.use_pallas)
        return out[None]

    return jax.jit(jax.shard_map(wire, mesh=tr.mesh, in_specs=P(axes),
                                 out_specs=P(axes), check_vma=False))


def error_moments(tr, sync: str):
    """The jitted statistics of ``sync_check``: from the workers'
    gradients and their aggregate, as (M, d) rows, to [||e||^2, its
    expectation, its variance, <e, m>, its variance]."""
    scheme = tr.tcfg.scheme
    levels = scheme.init_state().levels
    bucket = scheme.bucket_size
    axes = data_axes(tr)
    M = tr.mesh.size

    def stats(g, out):
        g = g[0]
        m = jax.lax.psum(g, axes) / M

        def block(x):
            gb, ob, mb = x
            e = ob - mb
            s2, s4 = rounding_moments(gb, levels, scheme.norm_type)
            # e is the mean of M independent errors: per coordinate
            # E[e^2] = S2 / M^2 with S2 the sum of the workers' s2, and
            # Var[e^2] = (sum s4 - 3 sum s2^2 + 2 S2^2) / M^4, which
            # S2^2 <= M sum s2^2 bounds by worker-local sums (equal at
            # M = 1)
            sums = [jnp.sum(e * e), jnp.sum(s2),
                    jnp.sum(s4 + (2 * M - 3) * s2 * s2),
                    jnp.sum(e * mb), jnp.sum(mb * mb * s2)]
            if sync == "two_phase":
                t2, t4 = rounding_moments(
                    mb, uniform_levels(TWO_PHASE_BITS), NORM_LINF)
                sums += [jnp.sum(t2), jnp.sum(t4 - t2 * t2),
                         jnp.sum(mb * mb * t2)]
            return jnp.stack(sums)

        p = jnp.sum(jax.lax.map(block, (blocks(g, bucket),
                                        blocks(out[0], bucket),
                                        blocks(m, bucket))), axis=0)
        err, ip = p[0], p[3]
        want = jax.lax.psum(p[1], axes) / M**2
        var = jax.lax.psum(p[2], axes) / M**4
        var_ip = jax.lax.psum(p[4], axes) / M**2
        if sync == "two_phase":
            want, var, var_ip = want + p[5], var + p[6], var_ip + p[7]
        return jnp.stack([err, want, var, ip, var_ip])

    return jax.jit(jax.shard_map(stats, mesh=tr.mesh,
                                 in_specs=(P(axes), P(axes)),
                                 out_specs=P(), check_vma=False))


def sync_check(tr, flats, sync: str) -> None:
    """The workers' step-0 gradients through the ``sync`` wire against
    their fp32 mean m.

    Each worker rounds its gradient without bias and independently of
    the others, so the aggregate's error e = out - m has mean zero and a
    per-coordinate variance the grid fixes.  Two sums are held to that
    within ``Z_MAX`` standard deviations: ||e||^2 against its
    expectation, and <e, m> against zero (the deviation of ||e||^2 is
    bounded from above for M > 1, which only widens its bound).  A wire that loses, zeroes or
    rescales the aggregate, or ships it unrounded, fails one of them.
    two_phase adds the re-rounding of the aggregate on its 8-bit L-inf
    grid, whose moments are taken at m (it runs on m plus the first
    hop's error, a correction far inside the bound).
    """
    with jax.set_mesh(tr.mesh):
        out = aggregate(tr, sync)(flats)
        err, want, var, ip, var_ip = (
            float(x) for x in error_moments(tr, sync)(flats, out))
        del out
    z_err = (err - want) / math.sqrt(var)
    z_ip = ip / math.sqrt(var_ip)
    print(f"sync {sync} M={tr.mesh.size}: |out-m|^2={err!r} expected={want!r} "
          f"z={z_err!r}; <out-m,m>={ip!r} z={z_ip!r}", flush=True)
    check(abs(z_err) <= Z_MAX and abs(z_ip) <= Z_MAX,
          f"{sync}: the aggregate's error is off the scheme's rounding "
          f"variance (z {z_err:.3g}, {z_ip:.3g})")


def compare_kernels(flat, levels) -> None:
    """Pallas kernels against kernels/ref.py on a real gradient: codes
    bit-equal for the same uniforms, decode and statistics allclose.
    The references are jitted so their temporaries fuse and fit."""
    levels = jnp.asarray(levels, jnp.float32)
    quantize_ref = jax.jit(ref.quantize_ref, static_argnums=3)
    dequantize_ref = jax.jit(ref.dequantize_ref)
    stats_ref = jax.jit(ref.bucket_stats_ref, static_argnums=1)
    for bs in BUCKETS:
        vb = pad_to_buckets(flat, bs)
        u = jax.random.uniform(jax.random.PRNGKey(bs), vb.shape, jnp.float32)
        codes, norms = ops.quantize_op(vb, u, levels, use_pallas=True)
        rcodes, rnorms = quantize_ref(vb, u, levels, "l2")
        # Mosaic and XLA may sum a bucket's squares in different orders:
        # norms agree to rounding, and the codes are held bit-equal to
        # the reference rounding against the same norms
        loose = int(jnp.sum(codes != rcodes))
        norms_ok = bool(jnp.allclose(norms, rnorms, rtol=1e-6))
        norm_err = float(jnp.max(jnp.abs(norms - rnorms) /
                                 jnp.maximum(rnorms, 1e-30)))
        norm_bits = int(jnp.sum(norms != rnorms))
        bad = int(jnp.sum(codes != quantize_ref(vb, u, levels, "l2",
                                                norms)[0]))
        del u, codes, norms
        deq = ops.dequantize_op(rcodes, rnorms, levels, use_pallas=True)
        rdeq = dequantize_ref(rcodes, rnorms, levels)
        deq_ok = bool(jnp.allclose(deq, rdeq, rtol=1e-6, atol=0.0))
        deq_err = float(jnp.max(jnp.abs(deq - rdeq)))
        del deq, rdeq, rcodes, rnorms
        stats_ok = all(
            bool(jnp.allclose(a, b, rtol=1e-5, atol=1e-7))
            for a, b in zip(ops.bucket_stats_op(vb, use_pallas=True),
                            stats_ref(vb, "l2")))
        del vb
        print(f"kernels bucket={bs} buckets={-(-flat.shape[0] // bs)} "
              f"code_mismatches={bad} norms_not_bit_equal={norm_bits} "
              f"code_mismatches_own_norms={loose} "
              f"norm_max_rel_err={norm_err!r} "
              f"dequantize_max_abs_err={deq_err!r} "
              f"bucket_stats_allclose={stats_ok}", flush=True)
        check(bad == 0, f"bucket {bs}: {bad} codes differ from the reference")
        check(norms_ok, f"bucket {bs}: norms differ from the reference")
        check(deq_ok, f"bucket {bs}: dequantize differs from the reference")
        check(stats_ok, f"bucket {bs}: bucket stats differ from the reference")


def one_chip(train_args, mesh, kernels: bool) -> None:
    check(mesh.size == 1, f"the mesh spans {mesh.size} devices, not 1")
    args, res = train_run(train_args, mesh, "--sync", "all_gather")
    quant = report_run("all_gather", args, res)
    print(f"peak_bytes_in_use={peak_bytes()!r}", flush=True)
    check(quant["kernels"] == kernels,
          f"tpu_custom_call in the compiled step: {quant['kernels']}")
    check(quant["updated"], "no level update inside the run")
    trainer = res.trainer
    del res
    flats = step0_gradients(trainer)
    compare_kernels(flats.reshape(-1), quant["levels"])
    sync_check(trainer, flats, "all_gather")
    del flats
    args, res = train_run(train_args, mesh, "--sync", "fp32", "--steps", "1")
    fp32 = report_run("fp32", args, res)
    print(f"step0 loss all_gather={quant['losses'][0]!r} "
          f"fp32={fp32['losses'][0]!r}", flush=True)
    check(quant["losses"][0] == fp32["losses"][0],
          "step-0 loss differs between the quantized and the fp32 step")


def four_chips(train_args, mesh, kernels: bool) -> None:
    """DP = 4: the two_phase aggregate of the four workers' step-0
    gradients against its rounding variance, then the two_phase wire
    against fp32 psum on the same batches (step 0's loss must agree
    exactly).  The aggregate check runs first: it is the part a run cut
    by its time limit should not lose."""
    check(mesh.size == 4, f"the mesh spans {mesh.size} devices, not 4")
    print("dp4 all_gather: not run; its (M, n) decode needs more memory "
          "than a chip has at these widths", flush=True)
    steps = ["--steps", str(DP4_STEPS)]
    args = train.parse_args([*train_args, "--sync", "two_phase", *steps])
    trainer = train.build(args, mesh)
    sync_check(trainer, step0_gradients(trainer), "two_phase")
    args, res = train_run(train_args, mesh, "--sync", "fp32", *steps)
    base = report_run("dp4 fp32", args, res)["losses"]
    del res
    args, res = train_run(train_args, mesh, "--sync", "two_phase", *steps)
    run = report_run("dp4 two_phase", args, res)
    del res
    print(f"dp4 two_phase peak_bytes_in_use={peak_bytes()!r}", flush=True)
    check(run["kernels"] == kernels,
          f"dp4 two_phase: tpu_custom_call {run['kernels']}")
    print(f"dp4 two_phase step0 loss {run['losses'][0]!r} vs fp32 "
          f"{base[0]!r}", flush=True)
    check(run["losses"][0] == base[0], "dp4 two_phase: step-0 loss differs")


def main(argv=None, *, platform: str = "tpu", train_args=None) -> int:
    """``platform`` and ``train_args`` let a test run the phases on the
    CPU at a small size, where the kernels are interpreted and the step
    holds no ``tpu_custom_call``; the command line always takes the
    defaults."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args(argv)

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != platform:
        print(f"chip_smoke: needs a {platform} device, found "
              f"{dev.platform!r}; no phase ran", file=sys.stderr)
        return 2
    count = len(jax.devices())
    if count < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
              f"devices, found {count}", file=sys.stderr)
        return 2

    if train_args is None:
        full = configs.get_config(ARCH).num_layers
        print(f"depth cut: {ARCH} num_layers {full} -> {LAYERS} "
              f"(widths unchanged) to fit one chip's HBM", flush=True)
        train_args = [*TRAIN_ARGS, "--layers", str(LAYERS)]
    print(f"device: {dev.platform} {dev.device_kind} x{count}", flush=True)
    mesh = make_local_mesh(devices=jax.devices()[:opts.chips])
    try:
        (four_chips if opts.chips == 4 else one_chip)(
            train_args, mesh, kernels=platform == "tpu")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
