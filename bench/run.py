#!/usr/bin/env python3
"""Benchmark of adaptively quantized data-parallel training on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``.  It builds the trainer
through the program's normal path (``repro.launch.train.build``), makes
the weights on the device from the seed with the reference module that
the configuration names (``bench/reference/<reference>.py``) and the
token batches with its own generator, compiles the cell's one step
shape into the persistent compilation cache (``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` is set), and drives the compiled
step through the job's checked set-up steps.  It then measures whole
steps for at least ``--seconds`` (never fewer than one), the window
ending when the last step's state is ready.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
traces the window with the profiler and reports its per-layer metrics,
the device's busy time and the breakdown.  Either way, after the window
the program's state is freed and the plain reference, that module among
it, follows the checked steps (``bench/check.py``); ``correct`` says
whether every compared number kept to its limit.  The numbers and limits
are the last lines of standard error and the last key of the result,
which is the last line of standard output.

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for, where the program is missing, or where the
configuration names no reference module that exists and exports the
architecture interface (``bench/reference/__init__.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# read when jax loads the TPU runtime; unset, its logs go outside the
# checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


# steps the window keeps in flight behind the first
QUEUED = 2


class SetupError(Exception):
    """The run cannot be made: no result is printed."""


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def launcher_args(cell) -> list:
    cfg, wire, job = cell.config, cell.wire, cell.job
    prog = cfg["program"]
    args = ["--arch", prog["arch"], "--batch", str(job["global_batch"]),
            "--seq", str(job["seq_len"]), "--data", "uniform",
            "--optim", job["optimizer"], "--lr", repr(job["lr"]),
            "--update-at", ",".join(str(t) for t in job["update_at"]),
            "--sync", wire["sync"], "--scheme", wire["scheme"]]
    if prog.get("smoke"):
        args.append("--smoke")
    if prog.get("layers"):
        args += ["--layers", str(prog["layers"])]
    if wire["scheme"] != "fp32":
        args += ["--bits", str(wire["bits"]), "--bucket", str(wire["bucket"])]
    args.append("--use-pallas" if prog.get("use_pallas", True)
                else "--no-use-pallas")
    return args


def check_program(tr, cell) -> None:
    """The program runs what the configuration, wire and job files
    state, or the run stops.  The configuration's reference module says
    which of the model's sizes and options to compare."""
    from bench import reference
    cfg, wire, job = cell.config, cell.wire, cell.job
    m, o, s = tr.model.cfg, tr.tcfg.optim, tr.tcfg.scheme
    # the architecture's sizes and options, then what every
    # configuration, wire and job states
    want = dict(reference.of(cfg).program_checks(cfg, m))
    want.update({
        "param_dtype": (m.param_dtype, cfg["param_dtype"]),
        "compute_dtype": (m.compute_dtype, cfg["compute_dtype"]),
        "optimizer": (o.name, job["optimizer"]),
        "lr": (o.lr, job["lr"]), "b1": (o.b1, job["b1"]),
        "b2": (o.b2, job["b2"]), "eps": (o.eps, job["eps"]),
        "weight_decay": (o.weight_decay, job["weight_decay"]),
        "update_at": (tuple(tr.tcfg.update_milestones),
                      tuple(job["update_at"])),
        "update_every": (tr.tcfg.update_every, 0),
        "scheme": (s.name, wire["scheme"]),
        "sync": (tr.tcfg.sync_mode, wire["sync"]),
    })
    if cell.quantized:
        want.update({
            "bits": (s.bits, wire["bits"]),
            "bucket": (s.bucket_size, wire["bucket"]),
            "norm": (s.norm_type, wire["norm"]),
            "stat_components": (s.max_stat_components,
                                wire["stat_components"]),
            "weighted": (s.weighted_stats, True),
        })
        if wire["scheme"] == "alq":
            want["alq_sweeps"] = (s.alq_sweeps, wire["alq_sweeps"])
        else:
            want["amq_steps"] = (s.amq_gd_steps, wire["amq_steps"])
    bad = {k: v for k, v in want.items() if v[0] != v[1]}
    if bad:
        raise SetupError(f"the program does not run what the cell's files "
                         f"state (program, file): {bad}")


def load_reader(path: str):
    name = "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """One run of one cell.  Per-layer readers (``bench/metrics``) read
    the attributes set here: ``cell``, ``device_kind``, ``chips``,
    ``tokens_per_s``, ``trace`` (the summary of ``tracereduce``, or
    None), ``memory`` (the compiled step's ``memory_analysis``),
    ``step_metrics`` (the program's metrics of the window's last step)
    and ``quant_nvar()``."""

    def __init__(self, spec, cell, opts, devices):
        self.spec, self.cell, self.opts = spec, cell, opts
        self.devices = devices
        self.device_kind = devices[0].device_kind
        self.chips = cell.chips
        self.trace = None
        self.tokens_per_s = None
        self.step_metrics = None
        self._nvar = None

    # ---- set-up -----------------------------------------------------------

    def build(self):
        import jax
        from repro.launch import train
        from repro.launch.mesh import make_local_mesh
        from repro.train.train_step import init_train_state
        from bench import generator as gen, reference

        cell, seed = self.cell, self.opts.seed
        arch = reference.of(cell.config)
        mesh = make_local_mesh(devices=self.devices[:cell.chips])
        tr = train.build(train.parse_args(launcher_args(cell)), mesh)
        check_program(tr, cell)
        self.tr = tr

        # the keys are arguments, not constants: one program for every seed
        def make(wkey, rkey):
            state = init_train_state(tr.model, tr.tcfg, wkey)
            params = arch.init_params(cell.config, wkey)
            shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
            theirs = jax.tree.map(lambda a: (a.shape, a.dtype), state.params)
            if shapes != theirs:
                raise SetupError("the program's parameter layout differs "
                                 "from the reference's")
            return state._replace(params=params, rng=rkey)

        with jax.set_mesh(tr.mesh):
            self.state = jax.jit(make, out_shardings=tr.state_shardings)(
                gen.key(seed, gen.WEIGHTS), gen.key(seed, gen.ROUNDING))
            t0 = time.perf_counter()
            self.compiled = tr.step.lower(self.state, self.batch(0)).compile()
        self.compile_s = time.perf_counter() - t0
        self.memory = self.compiled.memory_analysis()
        log(f"compiled the step in {self.compile_s:.1f}s: argument "
            f"{self.memory.argument_size_in_bytes} output "
            f"{self.memory.output_size_in_bytes} temp "
            f"{self.memory.temp_size_in_bytes} alias "
            f"{self.memory.alias_size_in_bytes} bytes")

    def batch(self, step: int):
        import jax
        from bench import generator as gen
        b = gen.batch(self.cell.job, self.cell.config["vocab_size"],
                      self.opts.seed, step)
        return jax.device_put(b, self.tr.batch_sharding)

    def checked_steps(self):
        """The job's first steps through the window's own call, read for
        the comparison with the reference."""
        import jax
        import numpy as np
        from bench import check

        job = self.cell.job
        losses, levels, first, agg = [], None, None, None
        for t in range(job["check_steps"]):
            self.state, met = self.compiled(self.state, self.batch(t))
            losses.append(float(met["loss"]))
            log(f"checked step {t}: loss {losses[-1]!r}")
            if t == 0:
                if self.cell.quantized:
                    levels = np.asarray(self.state.scheme_state.levels,
                                        np.float64)
                mu = jax.device_get(self.state.opt.mu)
                scale = 1.0 - job["b1"]
                first = np.array([np.linalg.norm(x.astype(np.float64))
                                  for x in jax.tree.leaves(mu)]) / scale
                agg = np.concatenate([x.reshape(-1) for x in
                                      jax.tree.leaves(mu)]) / np.float32(scale)
                del mu
        change = check.change_norms(self.cell.config, self.cell.wire, job,
                                    self.state.params, self.opts.seed)
        self.readings = check.Readings(losses, first, change, levels, agg)
        self.next_step = job["check_steps"]

    # ---- the window -------------------------------------------------------

    def window(self):
        """Whole steps for at least ``--seconds``.  The first step runs
        alone and times one step.  After it up to ``QUEUED`` steps are
        kept in flight, so that the host's dispatch, the next batch and
        a host stall shorter than the queued steps overlap the device,
        until the queued work ends past the window's length."""
        import jax
        trace = (jax.profiler.TraceAnnotation if self.opts.trace
                 else lambda name: contextlib.nullcontext())
        seconds = self.opts.seconds
        queue, done, ends = [], [], []
        t = self.next_step
        batch = self.batch(t)

        def dispatch():
            nonlocal batch, t
            with trace("dispatch"):
                self.state, met = self.compiled(self.state, batch)
            queue.append(met)
            t += 1
            with trace("batch"):
                batch = self.batch(t)

        def wait():
            met = queue.pop(0)
            with trace("wait"):
                met["loss"].block_until_ready()
            done.append(met)
            ends.append(time.perf_counter())

        # no collection pauses inside the window
        gc.collect()
        gc.disable()
        try:
            with trace("window"):
                t0 = time.perf_counter()
                dispatch()
                wait()
                one = ends[0] - t0
                while time.perf_counter() - t0 < seconds:
                    # keep up to QUEUED steps behind the one running while
                    # the work queued ends inside the window
                    while (len(queue) < QUEUED and time.perf_counter() - t0
                           + len(queue) * one < seconds):
                        dispatch()
                    if queue:
                        wait()
                while queue:
                    wait()
                jax.block_until_ready(self.state)
                t1 = time.perf_counter()
        finally:
            gc.enable()
        self.window_s = t1 - t0
        self.steps = len(done)
        job = self.cell.job
        self.tokens_per_s = (self.steps * job["global_batch"] * job["seq_len"]
                             / self.window_s)
        step_s = [b - a for a, b in zip([t0] + ends[:-1], ends)]
        metrics = jax.device_get(done)
        self.window_losses = [float(m["loss"]) for m in metrics]
        self.step_metrics = {k: float(v) for k, v in metrics[-1].items()}
        self.last_step = t - 1
        log(f"window: {self.steps} steps in {self.window_s!r}s, step_s "
            f"max {max(step_s)!r} min {min(step_s)!r}, losses "
            f"{self.window_losses[0]!r} .. {self.window_losses[-1]!r}")

    # ---- after the window -------------------------------------------------

    def quant_nvar(self):
        """sum E[(Q(g) - g)^2] / ||g||^2: g the reference gradient of the
        window's last batch at the final parameters, the expectation in
        closed form on the levels the run adapted."""
        if not self.cell.quantized:
            return None
        if self._nvar is None:
            import jax
            import jax.numpy as jnp
            from jax.flatten_util import ravel_pytree
            from bench import generator as gen, reference
            from bench.reference import wire as wire_ref
            cfg, wire = self.cell.config, self.cell.wire
            arch = reference.of(cfg)
            b = gen.batch(self.cell.job, cfg["vocab_size"], self.opts.seed,
                          self.last_step)

            @jax.jit
            def nvar(params, ids, labels, levels):
                g = jax.grad(lambda p: arch.loss(
                    cfg, p, ids, labels, "highest"))(params)
                flat = ravel_pytree(g)[0]
                return (wire_ref.rounding_variance(flat, levels,
                                                   wire["bucket"])
                        / jnp.sum(flat * flat))

            self._nvar = float(nvar(self.state.params, b["ids"], b["labels"],
                                    self.state.scheme_state.levels))
        return self._nvar

    def traced_window(self):
        import jax
        from bench import tracereduce
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                self.window()
            finally:
                jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)
            if len(paths) != 1:
                raise SetupError(f"expected one trace file, found {paths}")
            self.trace = tracereduce.summarize(tracereduce.events(paths[0]))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def per_layer(self) -> dict:
        out = {}
        for m in self.cell.per_layer:
            value = load_reader(self.spec.metric_path(m["name"]))(self)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def end_to_end(self, setup_s: float) -> dict:
        values = {"tokens_per_s": self.tokens_per_s, "setup_s": setup_s}
        out = {}
        for m in self.cell.end_to_end:
            if m["name"] not in values:
                raise SetupError(f"no measurement of {m['name']!r}")
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out

    def compare(self) -> dict:
        from bench import check
        cell = self.cell
        ref = check.follow(cell.config, cell.wire, cell.job, self.opts.seed)
        for line in check.leaf_report(cell.config, self.readings, ref):
            log(line)
        return check.numbers(self.readings, ref, cell.quantized)


def run(opts, *, platform: str = "tpu", root: str = ROOT) -> dict:
    from bench.spec import Spec
    spec = Spec(root)
    cell = spec.cell(opts.workload)
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SetupError(f"the program is not importable: {e}") from e
    devices = jax.devices()
    if devices[0].platform != platform:
        raise SetupError(f"needs a {platform} device, found "
                         f"{devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise SetupError(f"the cell needs {cell.chips} chips, found "
                         f"{len(devices)}")
    enable_compile_cache()
    # every program of the run, small ones too, is found in the cache by
    # the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    r = Run(spec, cell, opts, devices)
    r.build()
    r.checked_steps()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s!r} (compile {r.compile_s!r})")
    if opts.trace:
        r.traced_window()
    else:
        r.window()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    metrics = r.per_layer() if opts.trace else r.end_to_end(setup_s)
    # the program's state is freed before the reference runs
    r.state = None
    gc.collect()
    nums = r.compare()
    from bench import check
    correct = check.verdict(nums, cell.limits) and all(
        math.isfinite(x) for x in r.window_losses + r.readings.losses)
    failed = sum(not math.isfinite(x) for x in r.window_losses)
    device = {"platform": devices[0].platform, "kind": r.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": r.steps,
              "failed": failed, "metrics": metrics, "device": device}
    if opts.trace:
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        result["breakdown"] = {"device_ops": r.trace["device_ops"],
                               "idle_gaps": r.trace["idle_gaps"]}
    for k, v in nums.items():
        if k not in cell.limits:
            log(f"reading {k} {v!r} (not compared)")
    result["checks"] = {k: {"value": nums[k], "limit": v}
                        for k, v in cell.limits.items()}
    return result


def main(argv=None, *, platform: str = "tpu", root: str = ROOT) -> int:
    opts = parse_args(argv)
    try:
        result = run(opts, platform=platform, root=root)
    except (SetupError, KeyError, FileNotFoundError) as e:
        log(f"bench: no result: {e}")
        return 2
    for k, v in result["checks"].items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
