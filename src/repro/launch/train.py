"""Training launcher.

On real hardware this drives the production mesh; on CPU it runs the
reduced (smoke) configs end-to-end — same code path, mesh (dp, tp) built
from whatever devices exist.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
      --smoke --scheme alq --bits 3 --steps 50 --sync all_gather

The Pallas kernels are on by default; on CPU they run in the Pallas
interpreter, so CPU runs usually pass ``--no-use-pallas``.
``run(parse_args([...]))`` drives the same loop in-process and returns
the per-step metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.schemes import QuantScheme
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_axes
from repro.models.transformer import Model
from repro.train import checkpoint
from repro.train.data import DataConfig, Pipeline
from repro.train.optim import OptimConfig
from repro.train.train_step import (
    TrainConfig, TrainState, compress_state_specs, init_train_state,
    make_train_step, metric_specs)


def resume_state(ckpt_dir: str, state):
    """Auto-resume: (start_step, state) from the newest checkpoint in
    ``ckpt_dir`` (the FULL TrainState — optimizer moments, adapted
    levels, EF residual and all), or (0, state) for a fresh start."""
    found = checkpoint.restore_latest(ckpt_dir, state)
    if found is None:
        return 0, state
    step, restored = found
    print(f"resumed step {step} from "
          f"{checkpoint.step_path(ckpt_dir, step)}", flush=True)
    return step + 1, restored


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-proxy")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config for this arch")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the config's depth to N layers (0 = the "
                         "config's own depth); widths are never cut")
    ap.add_argument("--scheme", default="alq")
    ap.add_argument("--bits", type=int, default=3)
    ap.add_argument("--bucket", type=int, default=1024)
    ap.add_argument("--sync", default="all_gather",
                    choices=["fp32", "all_gather", "two_phase"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", default="markov",
                    choices=["markov", "uniform"],
                    help="markov: learnable bigram task (host table grows "
                         "as vocab^2, small vocabularies only); uniform: "
                         "i.i.d. tokens at any vocabulary")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optim", default="adamw", choices=["sgdm", "adamw"])
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--update-at", default="2,10")
    ap.add_argument("--codec", default="uniform",
                    choices=["uniform", "mixed_width", "entropy",
                             "entropy:uniform"],
                    help="wire codec: 'entropy' ships the entropy-coded "
                         "payload family (cold-start canonical-Huffman "
                         "table; bits/coord in the log is then the "
                         "MEASURED coded volume)")
    ap.add_argument("--widths", default="",
                    help="comma per-bucket scheme bits for "
                         "--codec mixed_width (cyclic pattern; empty = "
                         "the budget-neutral bits-1,bits+1 cycle)")
    ap.add_argument("--compress", default="plain",
                    help="compression algorithm around the codec "
                         "(repro.compress): plain | ef[:warmup] | "
                         "topk[:k]")
    ap.add_argument("--integrity", action="store_true", default=False,
                    help="lay per-bucket checksum words into the wire "
                         "payload; detected-corrupt buckets are "
                         "excluded from the aggregate")
    ap.add_argument("--save", default="")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory: enables periodic "
                         "TrainState saves and auto-resume from the "
                         "newest step_*.npz on restart")
    ap.add_argument("--save-every", type=int, default=0,
                    help="save the full TrainState to --ckpt-dir every "
                         "N steps (0 = only at the end)")
    ap.add_argument("--use-pallas", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="Pallas kernels on the quantization path "
                         "(interpreted on CPU; --no-use-pallas runs the "
                         "jnp reference instead)")
    return ap.parse_args(argv)


@dataclasses.dataclass
class Trainer:
    """Everything ``run`` builds before it compiles: the model and its
    train config, the data, the state and batch shardings over the mesh,
    ``init()`` (a fresh TrainState) and the jitted ``step``."""

    model: Model
    tcfg: TrainConfig
    pipe: Pipeline
    mesh: Any
    state_shardings: Any
    batch_sharding: Any
    init: Any
    step: Any


@dataclasses.dataclass
class RunResult:
    """What ``run`` measured.  ``metrics`` holds one dict of host floats
    per step; ``step_s`` the wall time of each step, ending when its
    metrics reached the host; ``compile_s`` the time to lower and
    compile the step; ``compiled`` the compiled step itself."""

    metrics: list
    step_s: list
    compile_s: float
    compiled: Any
    state: TrainState
    trainer: Trainer


def build(args, mesh) -> Trainer:
    """The trainer for ``args`` over ``mesh`` (axes as ``make_local_mesh``
    names them).  The step donates its state argument: the new state
    reuses its buffers."""
    data_axes, model_axis = mesh_axes(mesh)
    tp = mesh.shape[model_axis]
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    model = Model(cfg, tp=tp, dp=mesh.size // tp, data_axes=data_axes)
    tcfg = TrainConfig(
        scheme=QuantScheme(name=args.scheme, bits=args.bits,
                           bucket_size=args.bucket),
        optim=OptimConfig(name=args.optim, lr=args.lr, weight_decay=0.0),
        sync_mode=args.sync,
        update_milestones=tuple(int(x) for x in args.update_at.split(",")),
        update_every=0, microbatches=args.micro,
        use_pallas=args.use_pallas,
        codec=args.codec,
        mixed_width_pattern=tuple(
            int(x) for x in args.widths.split(",") if x),
        compress=args.compress,
        integrity=args.integrity)
    pipe = Pipeline(DataConfig(kind=args.data, vocab_size=cfg.vocab_size,
                               seq_len=args.seq, global_batch=args.batch))

    def init():
        return init_train_state(model, tcfg, jax.random.PRNGKey(0))

    shapes = jax.eval_shape(init)
    pspecs = model.param_specs()
    sspecs = TrainState(
        params=pspecs,
        opt=type(shapes.opt)(
            mu=pspecs, nu=None if shapes.opt.nu is None else pspecs,
            count=P()),
        scheme_state=jax.tree.map(lambda _: P(), shapes.scheme_state),
        step=P(), rng=P(),
        compress_state=compress_state_specs(shapes, data_axes))
    bspec = P(data_axes)
    step = jax.jit(
        jax.shard_map(make_train_step(model, tcfg, data_axes=data_axes),
                      mesh=mesh,
                      in_specs=(sspecs, {"ids": bspec, "labels": bspec}),
                      out_specs=(sspecs, metric_specs()), check_vma=False),
        donate_argnums=0)
    return Trainer(
        model, tcfg, pipe, mesh,
        jax.tree.map(lambda p: NamedSharding(mesh, p), sspecs),
        NamedSharding(mesh, bspec), init, step)


def run(args, mesh=None) -> RunResult:
    """Train for ``args.steps`` steps over ``mesh`` (default: every local
    device, ``--tp`` of them per model-parallel group)."""
    tr = build(args, make_local_mesh(tp=args.tp) if mesh is None else mesh)
    with jax.set_mesh(tr.mesh):
        state = jax.jit(tr.init, out_shardings=tr.state_shardings)()
        start = 0
        if args.ckpt_dir:
            start, state = resume_state(args.ckpt_dir, state)
            state = jax.device_put(state, tr.state_shardings)
        batch = jax.device_put(tr.pipe.batch(start), tr.batch_sharding)
        t0 = time.perf_counter()
        compiled = tr.step.lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        print(f"compiled the step over {tr.mesh.size} device(s) in "
              f"{compile_s:.1f}s", flush=True)
        # each step's host work in profiler spans, on the device
        # operations' clock when run under ``jax.profiler.trace``
        span = jax.profiler.TraceAnnotation
        history, step_s = [], []
        for t in range(start, args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=t):
                t0 = time.perf_counter()
                with span("dispatch"):
                    state, metrics = compiled(state, batch)
                with span("wait"):
                    m = {k: float(v)
                         for k, v in jax.device_get(metrics).items()}
                step_s.append(time.perf_counter() - t0)
                history.append(m)
                if t + 1 < args.steps:
                    with span("batch"):
                        batch = jax.device_put(tr.pipe.batch(t + 1),
                                               tr.batch_sharding)
                if args.ckpt_dir and (
                        (args.save_every > 0
                         and (t + 1) % args.save_every == 0)
                        or t == args.steps - 1):
                    with span("checkpoint"):
                        checkpoint.save_step(args.ckpt_dir, t, state)
            if t % 5 == 0 or t == args.steps - 1:
                extra = ("" if args.compress == "plain" else
                         f" |e|={m['residual_norm']:.3f}"
                         f" kept={m['kept_fraction']:.2f}")
                print(f"step {t:4d} loss={m['loss']:.4f} "
                      f"|g|={m['grad_norm']:.3f} "
                      f"bits/coord={m['comm_bits_per_coord']:.1f}"
                      f"{extra} "
                      f"levels={np.asarray(state.scheme_state.levels)[:4].round(3)}",
                      flush=True)
        dt = sum(step_s)
        print(f"done: {len(step_s)} steps in {dt:.1f}s "
              f"({dt / max(len(step_s), 1) * 1e3:.0f} ms/step)")
        if args.save:
            checkpoint.save(args.save, state.params)
            print(f"saved params to {args.save}")
    return RunResult(history, step_s, compile_s, compiled, state, tr)


def main(argv=None):
    enable_compile_cache()
    run(parse_args(argv))


if __name__ == "__main__":
    main()
