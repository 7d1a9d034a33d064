"""Declarative scenario registry + the simulated training loop.

A scenario is (scheme grid) x (topology grid) x (cluster model) x (model
config): each cell trains the real model (``repro.models`` +
``repro.train`` optimizers) for ``steps`` simulated steps with M logical
workers on one host, threading genuine ``SchemeState`` adaptation
(merged sufficient statistics across the simulated workers, the paper's
Algorithm 1 line 4) through the chosen aggregation topology, and records
a per-step trajectory: loss, wire bytes by direction, simulated
wall-clock from the cluster cost model, end-to-end aggregate error, and
gradient-statistics drift.

The per-worker protocol is the paper's own evaluation setup (Sec. 5:
"simulate training with M GPUs on a single GPU"), upgraded from plain
ENCODE/DECODE to full topology semantics: stragglers, dropout, and
per-hop re-quantization actually shape what the optimizer sees.

Everything is deterministic in the scenario config: model init, data,
quantization randomness, and cluster draws all derive from fixed seeds,
so the same scenario always emits a bit-identical trajectory (tested).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.compress import CompressionAlgorithm, make_algorithm
from repro.core.codec import (
    EntropyCodec,
    GradientCodec,
    MixedWidthCodec,
    codec_for_scheme,
    entropy_codec_from_gradient,
    mixed_widths_from_gradient,
)
from repro.core.schemes import QuantScheme, SchemeState
from repro.core.stats import expected_variance
from repro.dist.faults import FaultModel
from repro.dist.sync import gather_stats
from repro.launch.mesh import make_mesh
from repro.models import Model
from repro.train.data import DataConfig, Pipeline
from repro.train.optim import OptimConfig, OptState, apply_updates, init_opt_state

from .cluster import (
    ClusterConfig,
    init_cluster_state,
    sample_step,
    step_faults,
    step_time_ms,
)
from .topology import SIM_AXIS, TOPOLOGIES, run_compressed


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One named scenario grid (see SCENARIOS for the registry)."""

    name: str
    description: str = ""
    arch: str = "paper-proxy"
    # scheme specs: "alq" or "alq:4" (name:bits) — the grid's rows
    schemes: tuple = ("alq", "qsgdinf")
    topologies: tuple = TOPOLOGIES
    bits: int = 3
    bucket_size: int = 512
    steps: int = 10
    batch_per_worker: int = 2
    seq_len: int = 32
    lr: float = 1e-3
    optimizer: str = "adamw"
    update_milestones: tuple = (2, 6)   # level-adaptation steps
    sync_mode: str = "all_gather"       # allreduce topology wire mode
    server_bits: int | None = 8         # param_server downlink grid
    norm_dtype: str = "float32"
    # 'uniform' | 'mixed_width' | 'entropy' (the entropy-coded payload
    # family: canonical-Huffman table fit from a probe-step gradient's
    # level occupancies, RE-fit at every level-update milestone so the
    # table tracks the adapting grid — the measured wire bits/coord in
    # the trajectory then converge onto the metered
    # entropy_bits_per_coord)
    codec: str = "uniform"
    # static per-bucket scheme-bits pattern for the mixed-width codec;
    # empty = derive from a probe-step bit assignment (assign_mixed_widths
    # on the probe gradient's bucket statistics, budget = scheme bits).
    # Without an explicit pattern the assignment is RE-derived at every
    # level-update milestone, so the widths track drifting bucket stats.
    mixed_width_pattern: tuple = ()
    # compression-algorithm specs (repro.compress) — the grid's third
    # axis, crossed with schemes x topologies: 'plain' | 'ef[:warmup]'
    # | 'topk[:k]'
    compress: tuple = ("plain",)
    cluster: ClusterConfig = ClusterConfig()
    # opt-in wire integrity: every cell's codec lays per-bucket checksum
    # words into the payload and sync excludes detected-corrupt buckets
    # (core.codec ``integrity=``); requires a uniform/entropy codec
    integrity: bool = False
    # fault-model grid axis, crossed with schemes x topologies x
    # compress: each entry is a ``dist.faults.FaultModel`` or ``None``
    # (fault-free).  Wire faults (flips/drops/delays) hit the allreduce
    # collective through a FaultyTransport; crash/rejoin steps the
    # host-side Markov chain (``cluster.step_faults``) whose staleness
    # weights feed the MaskedTransport renormalization.
    fault_grid: tuple = (None,)
    seed: int = 0

    def make_scheme(self, spec: str) -> QuantScheme:
        name, _, b = spec.partition(":")
        return QuantScheme(
            name=name, bits=int(b) if b else self.bits,
            bucket_size=self.bucket_size, norm_dtype=self.norm_dtype)


SCENARIOS: dict[str, Scenario] = {}


def register(s: Scenario) -> Scenario:
    if s.name in SCENARIOS:
        raise ValueError(f"duplicate scenario {s.name!r}")
    SCENARIOS[s.name] = s
    return s


register(Scenario(
    name="paper_mlp",
    description="ALQ vs QSGDinf on the paper-scale proxy across all three "
                "topologies, homogeneous 4-worker cluster (the acceptance "
                "grid; also the CI smoke scenario).",
))
register(Scenario(
    name="stragglers",
    description="One-in-four steps a worker computes 4x slower: adaptive "
                "schemes keep their accuracy edge while every topology's "
                "simulated throughput degrades.",
    schemes=("alq", "qsgdinf"),
    cluster=ClusterConfig(straggler_prob=0.25, straggler_scale=4.0),
))
register(Scenario(
    name="hetero_bandwidth",
    description="Per-worker link speeds spanning 8x (2.5..20 Gb/s): "
                "param_server funnels through the server link while "
                "ring is gated by the slowest hop.",
    cluster=ClusterConfig(bandwidth_gbps=(2.5, 5.0, 10.0, 20.0)),
))
register(Scenario(
    name="dropout",
    description="Workers vanish for a step with p=0.2; aggregates "
                "renormalize over survivors (worker 0 never drops).",
    schemes=("alq",),
    cluster=ClusterConfig(dropout_prob=0.2),
))
register(Scenario(
    name="mixed_bits",
    description="Width sweep on the allreduce topology: the scheme grid "
                "crosses ALQ/QSGDinf with 2- and 4-bit grids.",
    schemes=("alq:2", "alq:4", "qsgdinf:2", "qsgdinf:4"),
    topologies=("allreduce",),
))
register(Scenario(
    name="ring_compounding",
    description="8-worker ring vs flat allreduce: per-hop re-quantization "
                "compounds error with ring distance; fp32 is the exact "
                "baseline.",
    schemes=("alq", "qsgdinf", "fp32"),
    topologies=("ring", "allreduce"),
    cluster=ClusterConfig(num_workers=8),
    steps=8,
))
register(Scenario(
    name="fp16_norms",
    description="The fp16 bucket-norm wire option end to end: identical "
                "grid to paper_mlp but with half-width norm side-channel.",
    norm_dtype="float16",
))
register(Scenario(
    name="mixed_width",
    description="MixedWidthCodec end to end: per-bucket wire widths from "
                "a probe-step bit assignment (high-norm/high-variance "
                "buckets get more levels at the scheme's mean-bits "
                "budget), threaded through allreduce and param_server.",
    schemes=("alq", "qsgdinf"),
    topologies=("allreduce", "param_server"),
    codec="mixed_width",
))
register(Scenario(
    name="entropy_coded",
    description="EntropyCodec end to end: the metered entropy cost "
                "realized as actual coded bytes.  The canonical-Huffman "
                "table is fit from a probe-step gradient and re-fit at "
                "every level-update milestone; the cost model bills "
                "makespan by the MEASURED per-bucket coded lengths, so "
                "measured bits/coord drop below the fixed-width plan "
                "and track entropy_bits_per_coord as the grid adapts.  "
                "Error feedback stacks on top unchanged (the ef cells "
                "are bit-exact with ef over the uniform codec).",
    schemes=("alq",),
    topologies=("allreduce", "param_server"),
    compress=("plain", "ef"),
    codec="entropy",
))
register(Scenario(
    name="ef_vs_plain",
    description="Error feedback at a 2-bit uniform grid: the residual "
                "memory re-injects each step's quantization error, so "
                "the CUMULATIVE aggregate error (cum_agg_err) stays "
                "bounded while the stateless 2-bit wire random-walks — "
                "EF's end-of-run cum_agg_err is strictly lower.",
    schemes=("qsgdinf:2",),
    topologies=("allreduce",),
    compress=("plain", "ef"),
    steps=10,
))
register(Scenario(
    name="fault_tolerance",
    description="The production allreduce under injected wire faults "
                "with integrity words on: per-word bit flips (~5% of "
                "buckets hit), whole-payload drops/delays, and a "
                "crash/rejoin Markov chain whose rejoining workers "
                "contribute staleness-weighted payloads.  Detected-"
                "corrupt buckets are excluded and renormalized, so the "
                "faulty cell's end-of-run loss stays within a few "
                "percent of the fault-free cell (acceptance: <= 10%).",
    schemes=("alq",),
    topologies=("allreduce",),
    integrity=True,
    # per-WORD flip probability: a 512-coordinate 3-bit bucket spans 65
    # wire words, so ~5% of buckets catch at least one flipped bit
    fault_grid=(None,
                FaultModel(flip_prob=0.0008, drop_prob=0.01,
                           delay_prob=0.01, crash_prob=0.08,
                           rejoin_prob=0.5, seed=13)),
    steps=10,
))
register(Scenario(
    name="topk_sweep",
    description="Top-k sparsification at the equal-wire-budget default "
                "k (index+value payloads cost what the dense symbols "
                "would): per-step error pays for the dropped support, "
                "but the EF memory keeps the cumulative aggregate error "
                "bounded where the dense stateless wire drifts.",
    schemes=("qsgdinf:2",),
    topologies=("allreduce", "param_server"),
    compress=("plain", "topk"),
    steps=10,
))


# ---------------------------------------------------------------------------
# one grid cell = (scheme, topology) trained for `steps` simulated steps
# ---------------------------------------------------------------------------

def _build_cell_step(model: Model, scheme: QuantScheme, scn: Scenario,
                     topo: str, mesh, use_pallas: bool,
                     algo: CompressionAlgorithm,
                     fault: FaultModel | None = None):
    """Jitted per-step function (runs inside shard_map on the 1x1 mesh so
    the model's internal psum('model') collectives resolve)."""
    M = scn.cluster.num_workers
    ocfg = OptimConfig(name=scn.optimizer, lr=scn.lr, weight_decay=0.0)
    pspecs = model.param_specs()
    # no dropout and no crash/rejoin -> active is statically all-ones;
    # passing None keeps the topologies on the exact production
    # reduction order (mean(0)).  Crash/rejoin staleness weights are
    # FRACTIONAL actives, so they also need the masked transport.
    masked = (scn.cluster.dropout_prob > 0
              or (fault is not None and fault.crash_prob > 0))

    def step(params, mu, nu, count, levels, multiplier, num_updates,
             ent_bits, resid, cstep, cum_err, ids, labels, key,
             do_update, active, fault_step):
        from repro.compress import CompressState
        scheme_state = SchemeState(levels, multiplier, num_updates,
                                   ent_bits)
        comp_state = CompressState(residual=resid, step=cstep)
        per = ids.shape[0] // M

        def worker_grad(w):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, w * per, per)
            loss, g = jax.value_and_grad(
                lambda p: model.loss(p, {"ids": sl(ids),
                                         "labels": sl(labels)}))(params)
            flat, _ = ravel_pytree(g)
            return loss, flat

        losses, flats = jax.lax.map(worker_grad, jnp.arange(M))

        res, comp_state = run_compressed(
            topo, flats, scheme, scheme_state, algo, comp_state, key,
            active=active if masked else None,
            sync_mode=scn.sync_mode, server_bits=scn.server_bits,
            use_pallas=use_pallas, fault=fault, fault_step=fault_step)

        # end-to-end aggregate error vs the exact (masked) fp32 mean —
        # the metric where ring's per-hop compounding becomes visible
        if masked:
            wmask = active / jnp.maximum(jnp.sum(active), 1.0)
            exact = jnp.tensordot(wmask, flats, axes=(0, 0))
        else:
            exact = flats.mean(0)
        agg = res.aggregate[0]
        agg_err = jnp.sum((agg - exact) ** 2)
        # cumulative aggregate-error VECTOR: the metric error feedback
        # bounds (sum_t agg_t - sum_t exact_t random-walks for stateless
        # wires; EF's residual telescopes it down to the final memory)
        cum_err = cum_err + (agg - exact)
        cum_agg_err = jnp.sum(cum_err ** 2)
        residual_norm = jnp.mean(jax.vmap(algo.residual_norm)(comp_state))

        # Algorithm 1 line 4 on the simulated cluster: sufficient
        # statistics merged ACROSS the M logical workers (vmap axes are
        # named axes, so merge_stats runs its real all_gather)
        if scheme.adaptive:
            def upd(s):
                stats = jax.vmap(
                    lambda f: gather_stats(f, scheme, axes=(SIM_AXIS,),
                                           use_pallas=use_pallas),
                    axis_name=SIM_AXIS)(flats)
                return scheme.update_state(
                    s, jax.tree.map(lambda a: a[0], stats))
            scheme_state = jax.lax.cond(do_update, upd, lambda s: s,
                                        scheme_state)

        # gradient-statistics drift: pooled truncated-normal fit of
        # worker 0's normalized magnitudes + the paper's Psi objective
        # evaluated at the CURRENT levels
        stats_now = gather_stats(flats[0], scheme, axes=(),
                                 use_pallas=use_pallas)
        drift_mu = jnp.sum(stats_now.gamma * stats_now.mu)
        drift_sigma = jnp.sum(stats_now.gamma * stats_now.sigma)
        psi = expected_variance(stats_now, scheme_state.levels)

        _, unravel = ravel_pytree(params)
        nu_state = nu if ocfg.name == "adamw" else None
        new_params, new_opt = apply_updates(
            ocfg, params, unravel(agg), OptState(mu, nu_state, count))
        new_nu = new_opt.nu if new_opt.nu is not None else nu

        metrics = {
            "loss": jnp.mean(losses),
            "agg_err": agg_err,
            "cum_agg_err": cum_agg_err,
            "quant_error": jnp.mean(res.quant_error),
            "residual_norm": residual_norm,
            "kept_fraction": jnp.float32(algo.kept_fraction),
            "grad_norm": jnp.sqrt(jnp.sum(exact ** 2)),
            "sent_bytes": res.sent_bytes,
            "recv_bytes": res.recv_bytes,
            "server_bytes": res.server_bytes,
            "hops": res.hops,
            "drift_mu": drift_mu,
            "drift_sigma": drift_sigma,
            "psi": psi,
            "levels": scheme_state.levels,
            "entropy_bits_per_coord": scheme_state.entropy_bits,
            # worker 0's shipped wire bits/coord (both directions):
            # MEASURED from the coded-length headers for the entropy
            # payload family, the static plan otherwise
            "measured_bits_per_coord": jnp.asarray(
                res.wire_bits_per_coord, jnp.float32)[0],
            "corrupt_fraction": jnp.asarray(res.corrupt_fraction,
                                            jnp.float32),
            "excluded_workers": jnp.asarray(res.excluded_workers,
                                            jnp.float32),
        }
        return (new_params, new_opt.mu, new_nu, new_opt.count,
                scheme_state.levels, scheme_state.multiplier,
                scheme_state.num_updates, scheme_state.entropy_bits,
                comp_state.residual, comp_state.step, cum_err,
                metrics)

    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, pspecs, pspecs, P(), P(), P(), P(), P(),
                  P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(pspecs, pspecs, pspecs, P(), P(), P(), P(), P(),
                   P(), P(), P(),
                   {k: P() for k in ("loss", "agg_err", "cum_agg_err",
                                     "quant_error", "residual_norm",
                                     "kept_fraction", "grad_norm",
                                     "sent_bytes", "recv_bytes",
                                     "server_bytes", "hops",
                                     "drift_mu", "drift_sigma", "psi",
                                     "levels", "entropy_bits_per_coord",
                                     "measured_bits_per_coord",
                                     "corrupt_fraction",
                                     "excluded_workers")}),
        check_vma=False)
    return jax.jit(smapped), ocfg


def _probe_gradient(model: Model, mesh, params, batch,
                    per_worker: int) -> jnp.ndarray:
    """Worker 0's probe-step gradient: one real backward on the first
    batch shard — the raw material of every host-level codec fit (the
    mixed-width bit assignment and the entropy-table refit)."""
    pspecs = model.param_specs()

    def gradf(p, ids, labels):
        g = jax.grad(lambda q: model.loss(
            q, {"ids": ids, "labels": labels}))(p)
        flat, _ = ravel_pytree(g)
        return flat

    f = jax.jit(jax.shard_map(
        gradf, mesh=mesh, in_specs=(pspecs, P(), P()), out_specs=P(),
        check_vma=False))
    with jax.set_mesh(mesh):
        return f(params, batch["ids"][:per_worker],
                 batch["labels"][:per_worker])


def _probe_mixed_widths(model: Model, scheme: QuantScheme, mesh,
                        params, batch, per_worker: int) -> tuple:
    """Per-bucket bit assignment from the probe gradient
    (``codec.mixed_widths_from_gradient``) — the static width pattern
    the whole cell then runs on."""
    flat = _probe_gradient(model, mesh, params, batch, per_worker)
    return mixed_widths_from_gradient(flat, scheme)


def _probe_entropy_codec(model: Model, scheme: QuantScheme, mesh,
                         params, batch, per_worker: int,
                         levels) -> EntropyCodec:
    """Canonical-Huffman table from the probe gradient's level
    occupancies at the CURRENT grid
    (``codec.entropy_codec_from_gradient``)."""
    flat = _probe_gradient(model, mesh, params, batch, per_worker)
    return entropy_codec_from_gradient(flat, scheme, levels)


def _make_cell_codec(scn: Scenario, scheme: QuantScheme, model: Model,
                     mesh, params, batch) -> GradientCodec | None:
    if not scheme.quantized:
        return None
    if scn.codec == "uniform":
        if not scn.integrity:
            return None          # default codec: exact production path
        return dataclasses.replace(codec_for_scheme(scheme),
                                   integrity=True)
    if scn.codec == "entropy":
        codec = _probe_entropy_codec(model, scheme, mesh, params, batch,
                                     scn.batch_per_worker,
                                     scheme.init_levels())
        if scn.integrity:
            codec = dataclasses.replace(codec, integrity=True)
        return codec
    if scn.codec != "mixed_width":
        raise ValueError(f"unknown scenario codec {scn.codec!r}")
    if scn.integrity:
        raise ValueError(
            "integrity=True needs a per-bucket checksum slot; the "
            "mixed-width payload family has none (use 'uniform' or "
            "'entropy')")
    widths = scn.mixed_width_pattern or _probe_mixed_widths(
        model, scheme, mesh, params, batch, scn.batch_per_worker)
    return MixedWidthCodec(bucket_size=scheme.bucket_size,
                           norm_type=scheme.norm_type,
                           norm_dtype=scheme.norm_dtype,
                           widths=tuple(int(b) for b in widths))


def _fixed_bits_per_coord(scn: Scenario, scheme: QuantScheme, topo: str,
                          d: int) -> float:
    """The fixed-width (uniform-codec) counterpart of the trajectory's
    per-worker ``measured_bits_per_coord`` for this topology — the plan
    an entropy-coded cell must beat.  Matches ``TopologyResult
    .wire_bits_per_coord``'s direction accounting: the gather hop for
    allreduce, uplink + downlink for param_server."""
    if not scheme.quantized:
        return 32.0
    from repro.core.codec import requant_codec
    from repro.dist import sync
    uc = codec_for_scheme(scheme)
    plan = uc.plan(d)
    if topo == "param_server":
        if scn.server_bits is None:
            down = 32.0
        else:
            c2 = requant_codec(uc, scn.server_bits)
            down = 8.0 * c2.plan_buckets(plan.nb).payload_bytes / d
        return float(plan.bits_per_coord + down)
    if topo == "ring":
        M = scn.cluster.num_workers
        splan = uc.plan(d, shards=M)
        return float(2.0 * (M - 1) * splan.payload_bytes * 8.0 / d)
    if scn.sync_mode == "two_phase":
        # reduce hop (scheme grid, sharded) + 8-bit broadcast hop —
        # the same two-hop sum _allreduce_two_phase reports
        M = scn.cluster.num_workers
        splan = uc.plan(d, shards=M)
        p2 = requant_codec(uc, sync.TWO_PHASE_BITS).plan_buckets(
            splan.shard_nb)
        return float(splan.bits_per_coord
                     + 32.0 * (p2.code_words + p2.norm_words) / d)
    return float(plan.bits_per_coord)


def _run_cell(scn: Scenario, spec: str, topo: str, comp_spec: str,
              steps: int, use_pallas: bool,
              fault: FaultModel | None = None) -> dict[str, Any]:
    scheme = scn.make_scheme(spec)
    cfg = configs.get_config(scn.arch)
    M = scn.cluster.num_workers
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, tp=1, dp=1)
    pipe = Pipeline(DataConfig(
        kind="markov", vocab_size=cfg.vocab_size, seq_len=scn.seq_len,
        global_batch=scn.batch_per_worker * M, seed=scn.seed))

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(scn.seed))
    codec = _make_cell_codec(scn, scheme, model, mesh, params,
                             pipe.batch(0))
    algo = make_algorithm(comp_spec, scheme, codec=codec)
    step_fn, ocfg = _build_cell_step(model, scheme, scn, topo, mesh,
                                     use_pallas, algo, fault=fault)
    opt = init_opt_state(ocfg, params)
    state = scheme.init_state()

    mu, nu, count = opt.mu, opt.nu, opt.count
    if nu is None:
        nu = jax.tree.map(jnp.zeros_like, mu)
    levels, mult, n_upd = state.levels, state.multiplier, state.num_updates
    ent = state.entropy_bits

    d = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    dres = d if algo.stateful else 0
    resid = jnp.zeros((M, dres), jnp.float32)
    cstep = jnp.zeros((M,), jnp.int32)
    cum_err = jnp.zeros((d,), jnp.float32)

    # widths / entropy tables are static (trace-time) layout, so
    # tracking drifting bucket stats happens at the HOST level: on every
    # level-update milestone the probe protocol re-runs on the current
    # parameters' gradient and the cell is re-built on the fresh
    # assignment (same cadence as ``maybe_update_levels``)
    reassign = (scn.codec == "mixed_width" and scheme.quantized
                and not scn.mixed_width_pattern)
    refit_table = scn.codec == "entropy" and scheme.quantized
    width_reassignments: list[dict[str, Any]] = []
    table_refits: list[dict[str, Any]] = []

    traj = []
    sim_time = 0.0
    wire_total = 0.0
    fault_events: list[dict[str, Any]] = []
    cstate = (init_cluster_state(M)
              if fault is not None and fault.crash_prob > 0 else None)
    with jax.set_mesh(mesh):
        for t in range(steps):
            batch = pipe.batch(t)
            compute_ms, active = sample_step(scn.cluster, t)
            if cstate is not None:
                # crash/rejoin Markov chain: crashed workers contribute
                # weight 0, rejoining ones the staleness weight 1/(1+k)
                # — fractional actives through the MaskedTransport
                cstate, fweight, events = step_faults(fault, cstate, t)
                active = active * fweight
                fault_events.extend(events)
            key = jax.random.fold_in(jax.random.PRNGKey(scn.seed + 7), t)
            (params, mu, nu, count, levels, mult, n_upd, ent,
             resid, cstep, cum_err, m) = step_fn(
                params, mu, nu, count, levels, mult, n_upd, ent,
                resid, cstep, cum_err,
                batch["ids"], batch["labels"], key,
                jnp.bool_(t in scn.update_milestones),
                jnp.asarray(active), jnp.int32(t))
            if reassign and t in scn.update_milestones:
                new_widths = _probe_mixed_widths(
                    model, scheme, mesh, params, batch,
                    scn.batch_per_worker)
                changed = tuple(new_widths) != tuple(codec.widths)
                width_reassignments.append({
                    "step": t,
                    "changed": changed,
                    "mean_width": float(np.mean(new_widths)),
                    "widths": [int(b) for b in new_widths],
                })
                if changed:
                    codec = dataclasses.replace(
                        codec, widths=tuple(int(b) for b in new_widths))
                    algo = make_algorithm(comp_spec, scheme, codec=codec)
                    step_fn, _ = _build_cell_step(
                        model, scheme, scn, topo, mesh, use_pallas, algo,
                        fault=fault)
            if refit_table and t in scn.update_milestones:
                # the levels just adapted inside step_fn: re-fit the
                # canonical-Huffman table to the NEW grid's occupancies
                # on a fresh probe gradient and rebuild the cell on it
                new_codec = _probe_entropy_codec(
                    model, scheme, mesh, params, batch,
                    scn.batch_per_worker, levels)
                changed = (new_codec.huff_lengths != codec.huff_lengths
                           or new_codec.huff_codes != codec.huff_codes)
                table_refits.append({
                    "step": t,
                    "changed": changed,
                    "max_code_bits": max(new_codec.huff_lengths),
                    "code_lengths": [int(l)
                                     for l in new_codec.huff_lengths],
                })
                if changed:
                    codec = new_codec
                    algo = make_algorithm(comp_spec, scheme, codec=codec)
                    step_fn, _ = _build_cell_step(
                        model, scheme, scn, topo, mesh, use_pallas, algo,
                        fault=fault)
            sent = np.asarray(m["sent_bytes"], np.float64)
            recv = np.asarray(m["recv_bytes"], np.float64)
            server = float(m["server_bytes"])
            hops = int(m["hops"])
            dt = step_time_ms(scn.cluster, compute_ms, active, sent, recv,
                              server, hops)
            if fault is not None and fault.delay_prob > 0:
                # a delayed payload stalls the aggregation window: bill
                # delay_ms once if any surviving worker's payload is late
                delayed = np.asarray(fault.delayed_workers(t, M))
                if bool(delayed[np.asarray(active) > 0].any()):
                    dt += fault.delay_ms
            sim_time += dt
            # total bytes crossing worker NICs (uniform across topologies;
            # the server's own link shows up in recv, not double-counted)
            step_wire = float(((sent + recv) * (active > 0)).sum())
            wire_total += step_wire
            traj.append({
                "step": t,
                "loss": float(m["loss"]),
                "sim_time_ms": dt,
                "cum_sim_time_ms": sim_time,
                "wire_sent_bytes": sent.tolist(),
                "wire_recv_bytes": recv.tolist(),
                "server_bytes": server,
                "hops": hops,
                "agg_err": float(m["agg_err"]),
                "cum_agg_err": float(m["cum_agg_err"]),
                "quant_error": float(m["quant_error"]),
                "residual_norm": float(m["residual_norm"]),
                "kept_fraction": float(m["kept_fraction"]),
                "grad_norm": float(m["grad_norm"]),
                "drift_mu": float(m["drift_mu"]),
                "drift_sigma": float(m["drift_sigma"]),
                "psi": float(m["psi"]),
                "entropy_bits_per_coord": float(
                    m["entropy_bits_per_coord"]),
                "measured_bits_per_coord": float(
                    m["measured_bits_per_coord"]),
                "levels": np.asarray(m["levels"]).tolist(),
                "compute_ms": np.asarray(compute_ms).tolist(),
                "active": [bool(a > 0) for a in active],
                "active_weight": [float(a) for a in np.asarray(active)],
                "corrupt_fraction": float(m["corrupt_fraction"]),
                "excluded_workers": float(m["excluded_workers"]),
            })
    return {
        "scheme": spec,
        "topology": topo,
        "compress": comp_spec,
        "bits": scheme.bits,
        "norm_dtype": scheme.norm_dtype,
        "codec": scn.codec if scheme.quantized else "uniform",
        "kept_fraction": float(algo.kept_fraction),
        "mean_width": (codec.mean_scheme_bits
                       if isinstance(codec, MixedWidthCodec)
                       else float(scheme.bits)),
        "width_reassignments": width_reassignments,
        "table_refits": table_refits,
        "integrity": bool(scn.integrity and scheme.quantized),
        "fault": dataclasses.asdict(fault) if fault is not None else None,
        "fault_events": fault_events,
        "fixed_bits_per_coord": _fixed_bits_per_coord(scn, scheme, topo,
                                                      d),
        "steps": traj,
        "totals": {
            "sim_time_ms": sim_time,
            "wire_bytes": wire_total,
            "final_loss": traj[-1]["loss"] if traj else None,
            "mean_agg_err": (float(np.mean([s["agg_err"] for s in traj]))
                             if traj else None),
            "final_cum_agg_err": (traj[-1]["cum_agg_err"] if traj
                                  else None),
            "mean_corrupt_fraction": (
                float(np.mean([s["corrupt_fraction"] for s in traj]))
                if traj else None),
        },
    }


def run_scenario(scn: Scenario, *, steps: int | None = None,
                 workers: int | None = None,
                 use_pallas: bool = False) -> dict[str, Any]:
    """Run every (scheme, topology, compress) cell of a scenario;
    JSON-ready dict."""
    if workers is not None:
        scn = dataclasses.replace(
            scn, cluster=dataclasses.replace(scn.cluster,
                                             num_workers=workers))
    n_steps = steps if steps is not None else scn.steps
    cells = []
    for spec in scn.schemes:
        for topo in scn.topologies:
            for comp in scn.compress:
                for fault in (scn.fault_grid or (None,)):
                    cells.append(_run_cell(scn, spec, topo, comp,
                                           n_steps, use_pallas,
                                           fault=fault))
    out = {
        "scenario": scn.name,
        "description": scn.description,
        "config": dataclasses.asdict(scn),
        "num_steps": n_steps,
        "cells": cells,
    }
    return out
