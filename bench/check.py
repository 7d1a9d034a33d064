"""What decides ``correct``: the program's first steps against the plain
reference.

Set-up drives the compiled step from the seed through the job's
``check_steps`` steps, on the window's own call and a new batch each
step.  The reference (``bench/reference``: the dense decoder in float32
at ``highest`` precision, the paper's wire with its own level update and
its own rounding, AdamW) follows the same steps from the same seeded
weights and batches.  The numbers compared, each against its limit in
``bench/limits/<cell>.json``:

    loss_gap_<t>   |loss_t - reference| / reference, for each step t
    grad_gap       the first gradient as the optimizer got it (AdamW's
                   first moment after step 0, over 1 - b1) against the
                   reference's, by the worst leaf
    grad_gap_total the same for the norm of the whole gradient, steady
                   where rounding noise in a leaf of few buckets sets
                   the worst leaf
    change_gap     the parameters' change over the checked steps
                   against the reference's, by the worst leaf
    levels_gap     (quantized wires) largest gap between the level grid
                   the step-0 update made and the reference's
    agg_err_gap    (quantized wires) | ||a - g||^2 / E||Q(g) - g||^2 - 1 |
                   with a the step-0 aggregate, g the reference gradient
                   and E the reference rounding's variance at g
    agg_rel_err    (fp32 wire) ||a - g|| / ||g||

"By the worst leaf": the largest |program's norm - reference's norm| of
a leaf, over the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of both.

The rounding of the program and of the reference are independent draws,
so the norms agree to the concentration of their sums, not bit for bit.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from bench import generator as gen
from bench.reference import adamw, dense_decoder, wire as wire_ref

# a leaf whose reference gradient norm is under this share of the
# median leaf's is left out of the leaf comparisons
NEGLIGIBLE_LEAF = 1e-3


@dataclasses.dataclass
class Readings:
    """One run of the checked steps, by the program or a reference."""

    losses: list
    first_grad: np.ndarray      # leaf norms
    change: np.ndarray          # leaf norms
    levels: np.ndarray | None   # the grid after the step-0 update
    aggregate: object           # step 0's aggregate, flat (host or device)


@dataclasses.dataclass
class Reference(Readings):
    grad: object = None            # step 0's gradient, flat, on device
    grad_norms: np.ndarray = None  # its leaf norms
    rounding_var: float = 0.0      # E||Q(g) - g||^2 at the reference grid


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Steps:
    """The reference's jitted pieces for one configuration, wire, job
    and precision, built once and reused across seeds."""

    def __init__(self, cfg: dict, wire: dict, job: dict,
                 precision: str = "highest"):
        self.cfg, self.wire, self.job = cfg, wire, job
        self.init = jax.jit(lambda k: dense_decoder.init_params(cfg, k))
        self.grad = jax.jit(jax.value_and_grad(
            lambda p, ids, labels: dense_decoder.loss(cfg, p, ids, labels,
                                                      precision)))
        self.opt = jax.jit(
            lambda p, q, m, v, t: adamw.update(job, p, q, m, v, t),
            donate_argnums=(0, 2, 3))
        self.adam_init = jax.jit(adamw.init)
        self.change = jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(jnp.subtract, p, dense_decoder.init_params(cfg, k))))
        if wire["scheme"] != "fp32":
            self.levels_of = jax.jit(
                lambda g: wire_ref.adapted_levels(g, wire))
            self.quantize = jax.jit(lambda g, lv, k: wire_ref.quantize(
                g, lv, k, wire["bucket"]))
            self.rounding_var = jax.jit(lambda g, lv: wire_ref.rounding_variance(
                g, lv, wire["bucket"]))

    def initial_levels(self):
        w = self.wire
        if w["scheme"] == "amq":
            return wire_ref.amq_levels_of(
                jnp.float32(w["initial_multiplier"]), w)
        return jnp.linspace(0.0, 1.0, wire_ref.num_levels(w),
                            dtype=jnp.float32)


_STEPS: dict = {}


def steps_for(cfg: dict, wire: dict, job: dict, precision: str) -> Steps:
    k = json.dumps([cfg, wire, job, precision], sort_keys=True)
    if k not in _STEPS:
        _STEPS[k] = Steps(cfg, wire, job, precision)
    return _STEPS[k]


def change_norms(cfg: dict, wire: dict, job: dict, params,
                 seed: int) -> np.ndarray:
    """Leaf norms of ``params`` minus the seed's initial weights."""
    st = steps_for(cfg, wire, job, "highest")
    return np.asarray(st.change(params, gen.key(seed, gen.WEIGHTS)),
                      np.float64)


def follow(cfg: dict, wire: dict, job: dict, seed: int, *,
           precision: str = "highest", stream: int = gen.REFERENCE,
           fault: str | None = None, reference: bool = True):
    """Run the reference through the checked steps from the seed.

    ``precision``, ``stream`` and ``fault`` put a variant of it in the
    program's place: another precision, another rounding draw, or one of
    the planted faults ``half_batch`` (the loss and gradient of the
    first half of the batch alone), ``no_exchange`` (the gradient passed
    on without going through the wire) and ``frozen`` (the step returns
    the parameters unchanged).
    """
    st = steps_for(cfg, wire, job, precision)
    quantized = wire["scheme"] != "fp32"
    rkey = gen.key(seed, stream)
    p = st.init(gen.key(seed, gen.WEIGHTS))
    m, v = st.adam_init(p)
    levels = st.initial_levels() if quantized else None
    losses, out = [], {}
    for t in range(job["check_steps"]):
        b = gen.batch(job, cfg["vocab_size"], seed, t)
        ids, labels = jnp.asarray(b["ids"]), jnp.asarray(b["labels"])
        if fault == "half_batch":
            half = ids.shape[0] // 2
            ids, labels = ids[:half], labels[:half]
        loss, g = st.grad(p, ids, labels)
        losses.append(float(loss))
        flat, unravel = ravel_pytree(g)
        if t == 0:
            out["grad_norms"] = np.asarray(leaf_norms(g), np.float64)
        del g
        q = flat
        if quantized:
            if t in job["update_at"]:
                levels = st.levels_of(flat)
            if fault != "no_exchange":
                q = st.quantize(flat, levels, jax.random.fold_in(rkey, t))
        if t == 0:
            out["levels"] = (None if levels is None
                             else np.asarray(levels, np.float64))
            out["first_grad"] = np.asarray(leaf_norms(unravel(q)),
                                           np.float64)
            # a reference keeps its gradient, a candidate its aggregate
            out["aggregate"] = None if reference else q
            if reference:
                out["grad"] = flat
                if quantized:
                    out["rounding_var"] = float(st.rounding_var(flat, levels))
        if fault != "frozen":
            p, m, v = st.opt(p, unravel(q), m, v, jnp.int32(t))
        del q, flat
    change = np.asarray(st.change(p, gen.key(seed, gen.WEIGHTS)), np.float64)
    del p, m, v
    base = dict(losses=losses, first_grad=out["first_grad"], change=change,
                levels=out["levels"], aggregate=out["aggregate"])
    if not reference:
        return Readings(**base)
    return Reference(**base, grad=out["grad"], grad_norms=out["grad_norms"],
                     rounding_var=out.get("rounding_var", 0.0))


def leaf_gaps(got, want, keep) -> np.ndarray:
    """|got - want| of each leaf over its own reference norm or the
    median leaf's, whichever is larger; NaN for leaves left out."""
    med = float(np.median(want[keep]))
    gaps = np.abs(got - want) / np.maximum(want, med)
    return np.where(keep, gaps, np.nan)


def _total_gap(got, want) -> float:
    a, b = np.sqrt(np.sum(got ** 2)), np.sqrt(np.sum(want ** 2))
    return float(abs(a - b) / b)


@jax.jit
def _sq_dist(a, b):
    return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))


def kept(ref: Reference) -> np.ndarray:
    return ref.grad_norms >= NEGLIGIBLE_LEAF * np.median(ref.grad_norms)


def numbers(got: Readings, ref: Reference, quantized: bool) -> dict:
    """The numbers of ``got`` against ``ref``: those of the module
    docstring.  A cell compares those its limits file names."""
    keep = kept(ref)
    out = {f"loss_gap_{t}": abs(a - b) / abs(b)
           for t, (a, b) in enumerate(zip(got.losses, ref.losses))}
    out["grad_gap"] = float(np.nanmax(
        leaf_gaps(got.first_grad, ref.first_grad, keep)))
    out["change_gap"] = float(np.nanmax(
        leaf_gaps(got.change, ref.change, keep)))
    out["grad_gap_total"] = _total_gap(got.first_grad[keep],
                                       ref.first_grad[keep])
    err, norm = (float(x) for x in _sq_dist(jnp.asarray(got.aggregate),
                                            ref.grad))
    if quantized:
        out["levels_gap"] = float(np.max(np.abs(got.levels - ref.levels)))
        out["agg_err_gap"] = abs(err / ref.rounding_var - 1.0)
    else:
        out["agg_rel_err"] = float(np.sqrt(err / norm))
    return out


def verdict(nums: dict, limits: dict) -> bool:
    """Every number the limits name is finite and within its limit."""
    missing = sorted(set(limits) - set(nums))
    if missing:
        raise KeyError(f"no reading of {missing}")
    return all(np.isfinite(nums[k]) and nums[k] <= v
               for k, v in limits.items())


def leaf_report(cfg: dict, got: Readings, ref: Reference) -> list:
    """One line per leaf: its name and size, the first gradient's and
    the change's norms of ``got`` and ``ref``, and their gaps."""
    from bench.reference.dense_decoder import param_shapes
    flat = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]
    keep = kept(ref)
    g = leaf_gaps(got.first_grad, ref.first_grad, keep)
    c = leaf_gaps(got.change, ref.change, keep)
    lines = []
    for i, (path, shape) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        lines.append(
            f"leaf {name} size {int(np.prod(shape))} first_grad "
            f"{got.first_grad[i]!r} ref {ref.first_grad[i]!r} gap {g[i]!r} "
            f"change {got.change[i]!r} ref {ref.change[i]!r} gap {c[i]!r}")
    if ref.levels is not None:
        lines.append(f"levels {got.levels.tolist()!r} ref "
                     f"{ref.levels.tolist()!r}")
    return lines
