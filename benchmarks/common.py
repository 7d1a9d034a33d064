"""Shared benchmark utilities: timing, CSV output, a tiny train loop
that simulates M data-parallel workers on one device (the paper's own
evaluation protocol, Sec. 5: "simulate training with 4 GPUs on a single
GPU by quantizing and dequantizing the gradient from 4 mini-batches")."""
from __future__ import annotations

import datetime
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.quantize import quantize as _quantize_fn
from repro.core.schemes import QuantScheme
from repro.dist.sync import gather_stats
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train.data import DataConfig, Pipeline
from repro.train.optim import OptimConfig, apply_updates, init_opt_state

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

ROWS = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def write_results(name: str, config: dict, metrics: dict) -> str:
    """Persist one benchmark run as ``BENCH_<name>.json`` at the repo
    root so successive runs leave a machine-readable perf trajectory.

    Schema: ``{name, config, metrics, timestamp}`` — ``config`` is the
    benchmark's parameterization, ``metrics`` its measured numbers (any
    JSON-serializable nesting; np/jnp scalars are coerced).
    """
    def coerce(x):
        if isinstance(x, dict):
            return {k: coerce(v) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return [coerce(v) for v in x]
        if isinstance(x, (np.generic, jnp.ndarray, np.ndarray)):
            arr = np.asarray(x)
            return arr.item() if arr.ndim == 0 else arr.tolist()
        return x

    payload = {
        "name": name,
        "config": coerce(config),
        "metrics": coerce(metrics),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {path}", flush=True)
    return path


def timeit(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6, out


class SimWorkers:
    """Paper-protocol simulation: M workers on one device.

    Each step draws M mini-batches, computes M local gradients, applies
    the scheme's ENCODE/DECODE to each, averages, and takes an SGD step.
    Levels adapt on the configured milestones from merged bucket stats.
    """

    def __init__(self, scheme: QuantScheme, M: int = 4, seed: int = 0,
                 lr: float = 1e-3, seq_len: int = 64, batch: int = 4,
                 arch: str = "paper-proxy"):
        self.scheme = scheme
        self.M = M
        cfg = configs.get_config(arch)
        self.cfg = cfg
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.model = Model(cfg, tp=1, dp=1)
        self.pipe = Pipeline(DataConfig(
            kind="markov", vocab_size=cfg.vocab_size, seq_len=seq_len,
            global_batch=batch * M, seed=seed))
        self.ocfg = OptimConfig(name="adamw", lr=lr, weight_decay=0.0)
        with jax.set_mesh(self.mesh):
            self.params = self.model.init(jax.random.PRNGKey(seed))
        self.opt = init_opt_state(self.ocfg, self.params)
        self.state = scheme.init_state()
        self._build()

    def _build(self):
        model, scheme, M = self.model, self.scheme, self.M
        pspecs = model.param_specs()
        from jax.flatten_util import ravel_pytree

        def step(params, opt_mu, opt_nu, opt_count, levels, ids, labels,
                 key, do_update):
            def worker_grad(w):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(
                    a, w * (ids.shape[0] // M), ids.shape[0] // M)
                l, g = jax.value_and_grad(
                    lambda p: model.loss(p, {"ids": sl(ids),
                                             "labels": sl(labels)}))(params)
                return l, g

            def one(w):
                l, g = worker_grad(w)
                flat, unravel = ravel_pytree(g)
                if scheme.quantized:
                    q = _quantize_fn(
                        flat, levels, jax.random.fold_in(key, w),
                        bucket_size=scheme.bucket_size,
                        norm_type=scheme.norm_type)
                else:
                    q = flat
                qerr = jnp.sum((q - flat) ** 2)
                return l, q, qerr, flat

            losses, qs, qerrs, flats = jax.lax.map(
                one, jnp.arange(M))
            mean_flat = qs.mean(0)

            # level adaptation from worker-0 stats (replicated protocol)
            new_levels = levels
            if scheme.adaptive:
                def upd(_):
                    stats = gather_stats(flats[0], scheme, axes=())
                    return scheme.update_state(
                        type(self.state)(levels, jnp.float32(0.5),
                                         jnp.int32(0)), stats).levels
                new_levels = jax.lax.cond(do_update, upd,
                                          lambda _: levels, None)

            _, unravel = ravel_pytree(params)
            grads = unravel(mean_flat)
            from repro.train.optim import OptState
            new_params, new_opt = apply_updates(
                self.ocfg, params, grads,
                OptState(opt_mu, opt_nu, opt_count))
            return (new_params, new_opt.mu, new_opt.nu, new_opt.count,
                    new_levels, losses.mean(), qerrs.mean(),
                    jnp.sum(mean_flat ** 2))

        smapped = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(pspecs, pspecs, pspecs, P(), P(), P("data"),
                      P("data"), P(), P()),
            out_specs=(pspecs, pspecs, pspecs, P(), P(), P(), P(), P()),
            check_vma=False)
        self._step = jax.jit(smapped)

    def run(self, steps: int, update_at=(2, 10)):
        metrics = {"loss": [], "qerr": []}
        levels = self.state.levels
        mu, nu, cnt = self.opt.mu, self.opt.nu, self.opt.count
        params = self.params
        with jax.set_mesh(self.mesh):
            for t in range(steps):
                b = self.pipe.batch(t)
                (params, mu, nu, cnt, levels, loss, qerr, _) = self._step(
                    params, mu, nu, cnt, levels, b["ids"], b["labels"],
                    jax.random.fold_in(jax.random.PRNGKey(1234), t),
                    jnp.bool_(t in update_at))
                metrics["loss"].append(float(loss))
                metrics["qerr"].append(float(qerr))
        self.params = params
        self.levels = levels
        return metrics

    def eval_accuracy(self, n_batches=4):
        """Next-token accuracy on held-out batches (val-acc proxy)."""
        model = self.model
        pspecs = model.param_specs()
        from repro.models.layers import lm_head_logits, rms_norm

        def acc_fn(params, ids, labels):
            x, _ = model.forward(params, ids)
            x = rms_norm(x, params["final_norm"], model.cfg.norm_eps)
            # greedy over full sequence: project all positions
            B, S, d = x.shape
            logits = lm_head_logits(model.ctx,
                                    params["lm_head"].squeeze(0),
                                    x.reshape(B * S, d),
                                    model.cfg.vocab_size)
            pred = jnp.argmax(logits, -1).reshape(B, S)
            return jnp.mean((pred == labels).astype(jnp.float32))

        f = jax.jit(jax.shard_map(
            acc_fn, mesh=self.mesh,
            in_specs=(pspecs, P("data"), P("data")), out_specs=P(),
            check_vma=False))
        accs = []
        with jax.set_mesh(self.mesh):
            for t in range(10_000, 10_000 + n_batches):
                b = self.pipe.batch(t)
                accs.append(float(f(self.params, b["ids"], b["labels"])))
        return float(np.mean(accs))
