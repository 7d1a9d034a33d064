"""Per-architecture smoke tests (assignment deliverable f): a REDUCED
variant of each assigned family (<=2-8 layers, d_model<=512, <=4 experts)
runs one forward + one quantized train step on CPU; output shapes are
checked and outputs are finite.  The FULL configs are exercised by the
dry-run only."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.schemes import QuantScheme
from repro.models import Model
from repro.train.optim import OptimConfig
from repro.train.train_step import (
    TrainConfig, TrainState, init_train_state, make_train_step)

ARCHS = list(configs.ARCH_NAMES)


def _mesh11():
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.get_smoke_config(arch)
    assert cfg.num_layers <= 8 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.num_experts <= 4
    mesh = _mesh11()
    model = Model(cfg, tp=1, dp=1)
    B, S = 2, 32
    ids = jax.random.randint(jax.random.PRNGKey(0), (B, S), 0,
                             cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"ids": ids, "labels": labels}
    vspec = None
    if cfg.cross_attn_every:
        batch["vision"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, 8, cfg.d_model), jnp.float32)
        vspec = P("data")

    tcfg = TrainConfig(
        scheme=QuantScheme(name="alq", bits=3, bucket_size=512),
        optim=OptimConfig(name="sgdm", lr=0.05),
        sync_mode="all_gather",
        update_milestones=(0,), update_every=0)
    step = make_train_step(model, tcfg, data_axes=("data",))

    pspecs = model.param_specs()
    bspecs = {k: (P("data") if k != "vision" else vspec) for k in batch}
    state_specs = None

    with jax.set_mesh(mesh):
        state = init_train_state(model, tcfg, jax.random.PRNGKey(3))
        state_specs = TrainState(
            params=pspecs,
            opt=type(state.opt)(mu=pspecs, nu=None, count=P()),
            scheme_state=jax.tree.map(lambda _: P(), state.scheme_state),
            step=P(), rng=P())
        fwd = jax.jit(jax.shard_map(
            lambda p, i, v: model.forward(p, i, v),
            in_specs=(pspecs, P("data"), vspec),
            out_specs=(P("data"), P()), check_vma=False))
        x, aux = fwd(state.params, ids, batch.get("vision"))
        assert x.shape == (B, S, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))

        from repro.train.train_step import metric_specs
        train = jax.jit(jax.shard_map(
            step, in_specs=(state_specs, bspecs),
            out_specs=(state_specs, metric_specs()),
            check_vma=False))
        new_state, metrics = train(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["loss"]) > 0
        assert int(new_state.step) == 1
        # params actually moved
        delta = sum(
            float(jnp.abs(a.astype(jnp.float32)
                          - b.astype(jnp.float32)).max())
            for a, b in zip(jax.tree.leaves(state.params),
                            jax.tree.leaves(new_state.params)))
        assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_metadata(arch):
    """The FULL configs carry the exact assigned dimensions + citation."""
    cfg = configs.get_config(arch)
    assert cfg.source, arch
    assert cfg.param_count() > 0
    assert cfg.num_layers % cfg.group_size == 0
