"""The plain references against the program at a small size on the
CPU, and the control and the planted faults failing the comparison."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

from bench import controls, generator as gen
from bench.reference import dense_decoder, wire as wire_ref
from bench.spec import Spec


def test_reference_loss_matches_the_program_model():
    from repro import configs
    from repro.launch.mesh import make_local_mesh
    from repro.models.transformer import Model
    from jax.sharding import PartitionSpec as P

    mesh = make_local_mesh(devices=jax.devices()[:1])
    model = Model(configs.get_smoke_config("qwen3-0.6b"), tp=1, dp=1)
    params = dense_decoder.init_params(tiny.CONFIG, gen.key(11, gen.WEIGHTS))
    b = gen.batch({"tokens": "uniform", "global_batch": 2, "seq_len": 16},
                  512, 11, 0)
    f = jax.jit(jax.shard_map(
        lambda p, ids, labels: model.loss(p, {"ids": ids, "labels": labels}),
        mesh=mesh, in_specs=(model.param_specs(), P(), P()), out_specs=P(),
        check_vma=False))
    with jax.set_mesh(mesh):
        got = float(f(params, b["ids"], b["labels"]))
    want = float(dense_decoder.loss(tiny.CONFIG, params, b["ids"],
                                    b["labels"]))
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("scheme", ["alq", "amq"])
def test_reference_levels_match_the_program_update(scheme):
    import json
    import os
    from repro.core.schemes import QuantScheme
    from repro.dist.sync import gather_stats

    name = {"alq": "alq3-allgather", "amq": "amq3-allgather"}[scheme]
    wire = json.load(open(os.path.join(tiny.ROOT, "bench", "wires",
                                       f"{name}.json")))
    sch = QuantScheme(name=scheme, bits=3, bucket_size=1024)
    k = jax.random.PRNGKey(4)
    g = jax.random.t(k, 3.0, (1024 * 300 + 77,))
    prog = sch.update_state(sch.init_state(),
                            gather_stats(g, sch, use_pallas=False)).levels
    ref = jax.jit(lambda g: wire_ref.adapted_levels(g, wire))(g)
    assert float(jnp.max(jnp.abs(prog - ref))) < 1e-5


def test_rounding_is_unbiased_with_the_stated_variance():
    levels = jnp.asarray([0.0, 0.1, 0.3, 1.0])
    g = jax.random.normal(jax.random.PRNGKey(0), (4096,))
    draws = jax.vmap(lambda k: wire_ref.quantize(g, levels, k, 1024))(
        jax.random.split(jax.random.PRNGKey(1), 400))
    err = np.asarray(draws - g)
    var = float(wire_ref.rounding_variance(g, levels, 1024))
    assert np.mean(np.sum(err ** 2, axis=1)) == pytest.approx(var, rel=0.02)
    assert abs(np.mean(err)) < 3 * np.sqrt(var / g.size / 400)


def test_control_and_faults_fail_sound_rounding_passes(tmp_path):
    root = str(tmp_path / "root")
    name = tiny.make_root(root, "alq")
    cell = Spec(root).cell(name)
    lines = {r["variant"]: r for r in controls.readings(
        cell, [5], ["self", "fp8", "half_batch", "no_exchange", "frozen"])}
    assert lines["self"]["fails"] == []
    for v in ("fp8", "half_batch", "no_exchange", "frozen"):
        assert lines[v]["fails"], v
    # a step that moves nothing reads 1 by the worst leaf
    assert lines["frozen"]["numbers"]["change_gap"] == pytest.approx(1.0)
