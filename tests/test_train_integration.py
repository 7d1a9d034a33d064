"""End-to-end training behaviour on CPU (single device, mesh 1x1):
loss decreases on a learnable synthetic task, ALQ levels adapt on the
schedule, and 8-bit quantized training tracks fp32 closely."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.schemes import QuantScheme
from repro.models import Model
from repro.train.data import DataConfig, Pipeline
from repro.train.optim import OptimConfig
from repro.train.train_step import (
    TrainConfig, TrainState, init_train_state, make_train_step)


def run_training(scheme_name, bits, steps=30, sync_mode="all_gather",
                 seed=0, lr=0.3):
    cfg = configs.get_config("paper-proxy")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(cfg, tp=1, dp=1)
    tcfg = TrainConfig(
        scheme=QuantScheme(name=scheme_name, bits=bits, bucket_size=1024),
        optim=OptimConfig(name="adamw", lr=1e-3, weight_decay=0.0),
        sync_mode=sync_mode,
        update_milestones=(2, 10), update_every=0)
    step_fn = make_train_step(model, tcfg, data_axes=("data",))
    pipe = Pipeline(DataConfig(kind="markov", vocab_size=cfg.vocab_size,
                               seq_len=64, global_batch=8, seed=seed))

    pspecs = model.param_specs()
    with jax.set_mesh(mesh):
        state = init_train_state(model, tcfg, jax.random.PRNGKey(seed))
        sspecs = TrainState(
            params=pspecs, opt=type(state.opt)(
                mu=pspecs,
                nu=None if state.opt.nu is None else pspecs, count=P()),
            scheme_state=jax.tree.map(lambda _: P(), state.scheme_state),
            step=P(), rng=P())
        from repro.train.train_step import metric_specs
        train = jax.jit(jax.shard_map(
            step_fn,
            in_specs=(sspecs, {"ids": P("data"), "labels": P("data")}),
            out_specs=(sspecs, metric_specs()),
            check_vma=False))
        losses, levels_hist = [], []
        for t in range(steps):
            state, metrics = train(state, pipe.batch(t))
            losses.append(float(metrics["loss"]))
            levels_hist.append(np.asarray(state.scheme_state.levels))
    return losses, levels_hist, state


def test_loss_decreases_with_alq():
    losses, levels, _ = run_training("alq", bits=3, steps=40)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_levels_adapt_on_schedule():
    _, levels, state = run_training("alq", bits=3, steps=15)
    # milestones at 2 and 10: levels must have moved after step 2
    assert np.allclose(levels[0], levels[1])
    assert not np.allclose(levels[1], levels[5])
    assert int(state.scheme_state.num_updates) == 2


def test_8bit_quantized_tracks_fp32():
    l_fp, _, _ = run_training("fp32", bits=8, steps=25)
    l_q8, _, _ = run_training("alq", bits=8, steps=25)
    # same data/seed; 8-bit adaptive quantization should track closely
    assert abs(np.mean(l_q8[-5:]) - np.mean(l_fp[-5:])) < 0.15


def test_two_phase_trains():
    losses, _, _ = run_training("alq", bits=4, steps=20,
                                sync_mode="two_phase")
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


@pytest.mark.parametrize("scheme", ["qsgdinf", "nuqsgd", "trn", "amq"])
def test_baselines_train(scheme):
    losses, _, _ = run_training(scheme, bits=3, steps=12)
    assert all(np.isfinite(losses))


def test_launcher_run_returns_step_metrics():
    """launch/train.run drives the CLI's loop in-process and returns one
    metrics dict and one wall time per step."""
    from repro.launch import train
    args = train.parse_args(["--arch", "paper-proxy", "--steps", "3",
                             "--batch", "4", "--seq", "32",
                             "--update-at", "1", "--no-use-pallas"])
    res = train.run(args)
    assert len(res.metrics) == len(res.step_s) == 3
    assert all(np.isfinite(m["loss"]) for m in res.metrics)
    assert res.compile_s > 0
    levels0 = res.trainer.tcfg.scheme.init_state().levels
    assert not np.array_equal(np.asarray(levels0),
                              np.asarray(res.state.scheme_state.levels))


def test_launcher_kernel_flag_defaults_on():
    from repro.launch import train
    assert train.parse_args([]).use_pallas
    assert not train.parse_args(["--no-use-pallas"]).use_pallas


def test_markov_source_refuses_published_vocabulary():
    V = configs.get_config("qwen3-0.6b").vocab_size
    with pytest.raises(ValueError, match="uniform"):
        Pipeline(DataConfig(kind="markov", vocab_size=V))
    b = Pipeline(DataConfig(kind="uniform", vocab_size=V, seq_len=8,
                            global_batch=2)).batch(0)
    assert b["ids"].shape == (2, 8)
    assert int(b["ids"].max()) < V
