"""Adaptive quantized data-parallel train step (Algorithm 1, end to end).

Per step, *inside one shard_map / jit*:
  1. local gradient from the device's batch shard (jax.grad inside
     shard_map -> genuinely local, no implicit psum over the data axes);
  2. on the paper's sparse schedule: fit bucket statistics (Pallas
     kernel), merge sufficient statistics across workers (tiny
     all_gather), run the ALQ/AMQ level update (lines 2-4);
  3. ENCODE -> collective -> DECODE -> average (lines 6-9) via
     dist.sync.quantized_allreduce in the configured wire mode;
  4. SGD-momentum / AdamW update (replicated across DP by construction
     since every worker decodes the same aggregate).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.compress import make_algorithm
from repro.core.codec import make_codec
from repro.core.schemes import QuantScheme, SchemeState
from repro.dist.sync import (
    compressed_allreduce, maybe_update_levels, quantized_allreduce)
from repro.models.transformer import Model
from .optim import OptimConfig, OptState, apply_updates, init_opt_state


class SyncMetricsLite(NamedTuple):
    """Wire metrics surfaced in real training logs — the same
    per-direction split + entropy + compression accounting ``repro.sim``
    reports.  Defaulted fields are float32 scalars (not Python floats)
    so ``metric_specs()`` harnesses see one metric dtype on every
    path."""

    comm_bits_per_coord: jnp.ndarray
    reduce_bits_per_coord: jnp.ndarray
    broadcast_bits_per_coord: jnp.ndarray
    entropy_bits_per_coord: jnp.ndarray
    residual_norm: jnp.ndarray = jnp.float32(0.0)
    kept_fraction: jnp.ndarray = jnp.float32(1.0)
    # wire-integrity accounting (dist.sync with ``integrity=`` plans):
    # fraction of (worker, bucket) payload slots excluded as corrupt,
    # and workers whose whole payload was excluded
    corrupt_fraction: jnp.ndarray = jnp.float32(0.0)
    excluded_workers: jnp.ndarray = jnp.float32(0.0)


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    scheme_state: SchemeState
    step: jnp.ndarray
    rng: jax.Array
    # repro.compress algorithm state (error-feedback residual + step
    # counter), checkpointed/restored like optimizer state.  ``None``
    # for stateless algorithms (the default 'plain'), keeping the state
    # pytree — and every existing checkpoint/spec construction —
    # unchanged unless a stateful algorithm is configured.  The residual
    # is PER-WORKER state: it carries a leading data-parallel axis
    # (dp, d), sharded over the data axes (``compress_state_specs``), so
    # each rank owns exactly its residual row.
    compress_state: Any = None


def compress_state_specs(state: TrainState, data_axes=("data",)):
    """shard_map specs for ``TrainState.compress_state``: the residual
    is sharded over the data axes (one row per DP rank), the step
    counter replicated.  ``None`` passes through for stateless
    algorithms."""
    from jax.sharding import PartitionSpec as P
    if state.compress_state is None:
        return None
    from repro.compress import CompressState
    return CompressState(residual=P(tuple(data_axes)), step=P())


# One ``jax.named_scope`` per layer of the data-parallel step, flat
# (never nested in one another).  Each scope becomes a segment of the
# ``op_name`` metadata of the compiled step's instructions, so a
# profile's device ops can be summed by layer; it adds no operation.
# ``dist/sync.py`` opens the wire's scopes (encode, collective, decode,
# level_update and its own counters' step_metrics).
LAYER_SCOPES = ("fwd_bwd", "ravel", "level_update", "encode", "collective",
                "decode", "optimizer", "step_metrics")

# every scalar train_step emits; launch/dryrun/test harnesses build their
# shard_map out_specs from this instead of hard-coding the key set
TRAIN_METRIC_KEYS = (
    "loss", "grad_norm", "comm_bits_per_coord",
    "reduce_bits_per_coord", "broadcast_bits_per_coord",
    "entropy_bits_per_coord", "residual_norm", "kept_fraction",
    "corrupt_fraction", "excluded_workers",
)


def metric_specs():
    """Replicated shard_map out_specs for the train-step metrics dict."""
    from jax.sharding import PartitionSpec as P
    return {k: P() for k in TRAIN_METRIC_KEYS}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    scheme: QuantScheme = QuantScheme()
    optim: OptimConfig = OptimConfig()
    sync_mode: str = "all_gather"       # fp32 | all_gather | two_phase
    update_milestones: tuple = (100, 2000)
    update_every: int = 10_000          # additionally every k steps
    use_pallas: bool = True
    microbatches: int = 1               # grad accumulation (activation mem)
    # wire codec of the DP allreduce path ('uniform' | 'mixed_width' |
    # 'entropy[:base]' — the entropy-coded payload family with the
    # cold-start canonical-Huffman table; comm_bits_per_coord then
    # reports the MEASURED coded volume).  FSDP models configure their
    # backward wire separately via ``Model(fsdp_codec=...)`` — train
    # metrics report whichever codec actually ships.
    codec: str = "uniform"
    # static per-bucket scheme-bits pattern for codec='mixed_width'
    # (tiled over the gradient's buckets; e.g. assign_mixed_widths
    # output).  Empty = the budget-neutral (bits-1, bits+1) cycle.
    mixed_width_pattern: tuple = ()
    # compression algorithm around the codec (repro.compress):
    # 'plain' | 'ef[:warmup_steps]' | 'topk[:k]'.  Drives the DP
    # allreduce path; for FSDP backward error feedback see
    # ``dist.fsdp.make_gather(algorithm=...)``.
    compress: str = "plain"
    # opt-in wire integrity: per-bucket checksum words in the payload;
    # dist.sync excludes detected-corrupt buckets from the aggregate
    # and reports corrupt_fraction / excluded_workers in the metrics
    integrity: bool = False


def _make_algo(tcfg: TrainConfig):
    if not tcfg.scheme.quantized:
        return None
    # None = the scheme's uniform codec; only a non-default codec (or
    # an integrity-on plan) is passed explicitly (make_algorithm rejects
    # codec overrides for 'topk', which owns its SparseCodec)
    codec = None
    if tcfg.codec != "uniform" or tcfg.integrity:
        codec = make_codec(tcfg.scheme, tcfg.codec,
                           tcfg.mixed_width_pattern,
                           integrity=tcfg.integrity)
    return make_algorithm(tcfg.compress, tcfg.scheme, codec=codec)


def init_train_state(model: Model, tcfg: TrainConfig, key) -> TrainState:
    params = model.init(key)
    algo = _make_algo(tcfg)
    compress_state = None
    if algo is not None and algo.stateful:
        if model.param_mode == "fsdp":
            raise NotImplementedError(
                "stateful compression on the FSDP path is wired at the "
                "gather level (dist.fsdp.make_gather(algorithm=...)), "
                "not through TrainConfig.compress")
        d = sum(int(x.size) for x in jax.tree.leaves(params))
        cs = algo.init_state(d)
        # one residual row per DP rank (sharded over the data axes)
        compress_state = cs._replace(
            residual=jnp.zeros((model.dp, d), jnp.float32))
    return TrainState(
        params=params,
        opt=init_opt_state(tcfg.optim, params),
        scheme_state=tcfg.scheme.init_state(),
        step=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(0),
        compress_state=compress_state,
    )


def _is_update_step(tcfg: TrainConfig, step):
    hit = jnp.zeros((), bool)
    for m in tcfg.update_milestones:
        hit |= step == m
    if tcfg.update_every > 0:
        hit |= (step > 0) & (step % tcfg.update_every == 0)
    return hit


def make_train_step(model: Model, tcfg: TrainConfig, *, data_axes=("data",)):
    """Returns train_step(state, batch) for use INSIDE shard_map."""
    scheme = tcfg.scheme
    algo = _make_algo(tcfg)
    codec = algo.codec if algo is not None else None

    def train_step(state: TrainState, batch):
        fsdp = model.param_mode == "fsdp"
        # worker-distinct randomness over the DP axes only (so grads of
        # TP-replicated params stay bit-identical across the model axis)
        data_rank0 = jnp.zeros((), jnp.int32)
        for ax in data_axes:
            data_rank0 = (data_rank0 * jax.lax.axis_size(ax)
                          + jax.lax.axis_index(ax))
        base_key = jax.random.fold_in(
            jax.random.fold_in(state.rng, state.step), data_rank0)
        sync_ctx = (state.scheme_state.levels, base_key) if fsdp else None

        k = tcfg.microbatches
        with jax.named_scope("fwd_bwd"):
            if k <= 1:
                def loss_fn(p):
                    return model.loss(p, batch, sync_ctx)

                loss, grads = jax.value_and_grad(loss_fn)(state.params)
            else:
                # gradient accumulation over k micro-batches (scan keeps
                # the live activation set to one micro-batch)
                micro = jax.tree.map(
                    lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:]),
                    batch)

                def micro_step(carry, mb):
                    loss_acc, gacc = carry
                    l, g = jax.value_and_grad(
                        lambda p: model.loss(p, mb, sync_ctx))(state.params)
                    gacc = jax.tree.map(lambda a, b: a + b, gacc, g)
                    return (loss_acc + l, gacc), None

                # accumulate in the parameter dtype (f32 for f32 masters;
                # bf16 for bf16-param configs like jamba — their grads
                # are quantized on the wire anyway)
                zeros = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), state.params)
                (loss, grads), _ = jax.lax.scan(
                    micro_step, (jnp.zeros((), jnp.float32), zeros), micro)
                loss = loss / k
                grads = jax.tree.map(lambda a: a / k, grads)

        new_comp = state.compress_state
        if fsdp:
            # gradients were already quantized-reduce-scattered inside the
            # FSDP gather's custom_vjp; levels adapt from one (flat,
            # already-sharded) slot's gradient — no full ravel copy.
            stats_src = grads["slots"][0].reshape(-1)
            scheme_state = maybe_update_levels(
                stats_src, scheme, state.scheme_state,
                _is_update_step(tcfg, state.step),
                axes=data_axes, use_pallas=tcfg.use_pallas)
            # per-direction wire cost of the backward reduce-scatter.
            # FSDP's wire codec is baked into the Model's gather
            # (``fsdp_codec``), NOT TrainConfig.codec (which drives the
            # DP allreduce path) — report what actually ships.
            fsdp_codec = getattr(model, "_fsdp_codec", codec)
            quantized_rs = scheme.quantized and fsdp_codec is not None
            wire = (fsdp_codec.nominal_bits_per_coord if quantized_rs
                    else 32.0)
            # flat slot/embed leaves were synced in the gather's vjp; the
            # small replicated leaves (final_norm) still need the DP mean
            M = 1
            for ax in data_axes:
                M *= jax.lax.axis_size(ax)
            grads_synced = dict(grads)
            grads_synced["final_norm"] = jax.lax.psum(
                grads["final_norm"], tuple(data_axes)) / M
            gn_sq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree.leaves(grads))
            grad_norm = jnp.sqrt(gn_sq)
            metrics = SyncMetricsLite(
                comm_bits_per_coord=jnp.float32(
                    2.0 * wire if quantized_rs else 32.0),
                reduce_bits_per_coord=jnp.float32(wire),
                broadcast_bits_per_coord=jnp.float32(
                    wire if quantized_rs else 0.0),
                entropy_bits_per_coord=jnp.asarray(
                    scheme_state.entropy_bits, jnp.float32))
        else:
            with jax.named_scope("ravel"):
                flat, unravel = ravel_pytree(grads)
            scheme_state = maybe_update_levels(
                flat, scheme, state.scheme_state,
                _is_update_step(tcfg, state.step),
                axes=data_axes, use_pallas=tcfg.use_pallas)
            if algo is None:  # fp32 / super_sgd: plain mean psum
                synced, metrics = quantized_allreduce(
                    flat, scheme, scheme_state, base_key,
                    axes=data_axes, mode=tcfg.sync_mode,
                    use_pallas=tcfg.use_pallas)
            else:
                cs = state.compress_state
                if cs is not None:
                    # inside shard_map each rank holds its (1, d) row of
                    # the data-axis-sharded residual
                    cs = cs._replace(residual=cs.residual[0])
                synced, new_comp, metrics = compressed_allreduce(
                    flat, scheme, scheme_state, algo, cs, base_key,
                    axes=data_axes, mode=tcfg.sync_mode,
                    use_pallas=tcfg.use_pallas)
                if new_comp is not None:
                    new_comp = new_comp._replace(
                        residual=new_comp.residual[None])
                    # per-rank residual magnitudes differ; report the
                    # replicated DP mean
                    with jax.named_scope("step_metrics"):
                        metrics = metrics._replace(
                            residual_norm=jax.lax.pmean(
                                jnp.asarray(metrics.residual_norm,
                                            jnp.float32),
                                tuple(data_axes)))
            with jax.named_scope("ravel"):
                grads_synced = unravel(synced)
            with jax.named_scope("step_metrics"):
                grad_norm = jnp.sqrt(jnp.sum(synced * synced))

        with jax.named_scope("optimizer"):
            new_params, new_opt = apply_updates(
                tcfg.optim, state.params, grads_synced, state.opt)

        new_state = TrainState(
            params=new_params, opt=new_opt, scheme_state=scheme_state,
            step=state.step + 1, rng=state.rng,
            compress_state=new_comp)
        with jax.named_scope("step_metrics"):
            out_metrics = {
                "loss": jax.lax.pmean(loss, tuple(data_axes)),
                "grad_norm": grad_norm,
                "comm_bits_per_coord": metrics.comm_bits_per_coord,
                "reduce_bits_per_coord": metrics.reduce_bits_per_coord,
                "broadcast_bits_per_coord": metrics.broadcast_bits_per_coord,
                "entropy_bits_per_coord": metrics.entropy_bits_per_coord,
                "residual_norm": jnp.asarray(metrics.residual_norm,
                                             jnp.float32),
                "kept_fraction": jnp.asarray(metrics.kept_fraction,
                                             jnp.float32),
                "corrupt_fraction": jnp.asarray(metrics.corrupt_fraction,
                                                jnp.float32),
                "excluded_workers": jnp.asarray(metrics.excluded_workers,
                                                jnp.float32),
            }
        return new_state, out_metrics

    return train_step
