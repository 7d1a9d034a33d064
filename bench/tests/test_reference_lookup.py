"""Each configuration names its architecture module in bench/reference,
and the harness makes the reference's weights, losses, counts and the
program's checks through that module alone."""
import collections
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

import tiny
from tiny import ROOT

from bench import check, reference, run, yardstick
from bench.reference import dense_decoder, ops
from bench.spec import Cell, Spec

CONFIGS = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(ROOT, "bench", "configs")) if f.endswith(".json"))
ARGS = ["--seed", "3000000019", "--seconds", "0.5", "--trace", "0"]


def read(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_a_whole_architecture(name):
    cfg = read("configs", f"{name}.json")
    mod = reference.of(cfg)
    assert mod.__name__ == f"bench.reference.{cfg['reference']}"
    for f in reference.INTERFACE:
        assert callable(getattr(mod, f)), f


def dense_num_coords(cfg):
    """The dense decoder's coordinates leaf by leaf, as the yardstick
    counted them before it read the architecture module."""
    shapes = dense_decoder.param_shapes(cfg)
    leaves = [shapes["embed"], shapes["lm_head"], shapes["final_norm"]]
    for slot in shapes["slots"]:
        leaves += [slot["norm1"], slot["norm2"], *slot["mixer"].values(),
                   *slot["ffn"].values()]
    return sum(math.prod(s) for s in leaves)


def dense_flops(cfg, seq_len):
    """The yardstick's closed form before it moved into the module."""
    s = dense_decoder.sizes(cfg)
    d, H, KV, hd, F, V, L = (s[k] for k in ("d", "H", "KV", "hd", "F",
                                            "V", "L"))
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    matmul = L * per_layer + d * V
    attention = L * 2 * 2 * H * hd * (seq_len + 1) / 2
    return 3.0 * (2.0 * matmul + attention)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "granite-3-2b"])
def test_counts_equal_the_dense_formulas(name):
    cfg = read("configs", f"{name}.json")
    assert yardstick.num_coords(cfg) == dense_num_coords(cfg)
    for seq_len in (1, 128, 2048):
        assert yardstick.model_flops_per_token(cfg, seq_len) == \
            dense_flops(cfg, seq_len)


# ---- a stub architecture, registered as bench/reference/stub_arch.py ----

STUB_CFG = {"name": "stub", "reference": "stub_arch", "vocab_size": 64,
            "width": 16}


def stub_module():
    """A one-layer model with a parameter tree of its own: an embedding,
    one tanh mixing matrix and an output matrix."""
    mod = types.ModuleType("bench.reference.stub_arch")
    mod.calls = collections.Counter()
    mod.precisions = set()

    def param_shapes(cfg):
        mod.calls["param_shapes"] += 1
        V, d = cfg["vocab_size"], cfg["width"]
        return {"tok": (V, d), "mix": {"w": (d, d)}, "out": (d, V)}

    def init_params(cfg, key):
        mod.calls["init_params"] += 1
        flat, tree = jax.tree_util.tree_flatten(
            param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree.unflatten(tree, [
            jax.random.normal(jax.random.fold_in(key, i), s) * s[0] ** -0.5
            for i, s in enumerate(flat)])

    def loss(cfg, params, ids, labels, precision):
        mod.calls["loss"] += 1
        mod.precisions.add(precision)
        h = jnp.tanh(ops.dot("bsd,de->bse", params["tok"][ids],
                             params["mix"]["w"], precision))
        logits = ops.dot("bsd,dv->bsv", h, params["out"], precision)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    def flops_per_token(cfg, seq_len):
        mod.calls["flops_per_token"] += 1
        V, d = cfg["vocab_size"], cfg["width"]
        return 6.0 * (d * d + d * V)

    def program_checks(cfg, m):
        mod.calls["program_checks"] += 1
        return {"width": (m.width, cfg["width"])}

    for f in (param_shapes, init_params, loss, flops_per_token,
              program_checks):
        setattr(mod, f.__name__, f)
    return mod


@pytest.fixture
def stub(monkeypatch):
    mod = stub_module()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    # no Steps built for an earlier registration
    monkeypatch.setattr(check, "_STEPS", {})
    return mod


def stub_cell():
    return Cell(name="stub.alq", chips=1,
                config=dict(STUB_CFG, param_dtype="float32",
                            compute_dtype="float32"),
                wire=read("wires", "alq3-allgather.json"),
                job={**read("jobs", "b4s128-uniform.json"), **tiny.JOB},
                limits={}, end_to_end=[], per_layer=[])


def test_the_reference_follows_the_named_module(stub):
    cell = stub_cell()
    steps = check.steps_for(cell.config, cell.wire, cell.job, "fp8")
    assert isinstance(steps, check.Steps)
    ref = check.follow(cell.config, cell.wire, cell.job, 7)
    got = check.follow(cell.config, cell.wire, cell.job, 7,
                       precision="fp8", reference=False)
    assert stub.calls["init_params"] and stub.calls["loss"]
    assert stub.precisions == {"highest", "fp8"}
    # the stub's three leaves, not the dense decoder's
    assert len(ref.first_grad) == len(ref.change) == len(got.change) == 3
    assert all(x > 0 for x in ref.losses)
    lines = check.leaf_report(cell.config, got, ref)
    assert [ln.split()[1:4] for ln in lines[:3]] == [
        ["['mix']['w']", "size", "256"], ["['out']", "size", "1024"],
        ["['tok']", "size", "1024"]]


def test_counts_follow_the_named_module(stub):
    assert yardstick.num_coords(STUB_CFG) == 16 * 16 + 2 * 64 * 16
    assert yardstick.model_flops_per_token(STUB_CFG, 16) == 6.0 * (
        16 * 16 + 16 * 64)
    assert stub.calls["param_shapes"] and stub.calls["flops_per_token"]


def test_quant_nvar_takes_the_named_modules_gradient(stub):
    from jax.flatten_util import ravel_pytree
    from bench import generator as gen
    from bench.reference import wire as wire_ref
    cell = stub_cell()
    params = stub.init_params(STUB_CFG, gen.key(7, gen.WEIGHTS))
    levels = jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)
    fake = types.SimpleNamespace(
        cell=cell, opts=types.SimpleNamespace(seed=7), last_step=4,
        _nvar=None, state=types.SimpleNamespace(
            params=params,
            scheme_state=types.SimpleNamespace(levels=levels)))
    nvar = run.Run.quant_nvar(fake)
    assert stub.precisions == {"highest"}
    b = gen.batch(cell.job, STUB_CFG["vocab_size"], 7, 4)
    g = ravel_pytree(jax.grad(lambda p: stub.loss(
        STUB_CFG, p, b["ids"], b["labels"], "highest"))(params))[0]
    want = wire_ref.rounding_variance(g, levels, 1024) / jnp.sum(g * g)
    assert nvar == pytest.approx(float(want), rel=1e-5)


def program_like(cell, width):
    """What ``check_program`` reads of a built trainer, as the cell's
    files state it, with the model's ``width``."""
    ns = types.SimpleNamespace
    cfg, wire, job = cell.config, cell.wire, cell.job
    return ns(
        model=ns(cfg=ns(width=width, param_dtype="float32",
                        compute_dtype="float32")),
        tcfg=ns(optim=ns(name=job["optimizer"], lr=job["lr"], b1=job["b1"],
                         b2=job["b2"], eps=job["eps"],
                         weight_decay=job["weight_decay"]),
                update_milestones=job["update_at"], update_every=0,
                sync_mode=wire["sync"],
                scheme=ns(name=wire["scheme"], bits=wire["bits"],
                          bucket_size=wire["bucket"], norm_type=wire["norm"],
                          max_stat_components=wire["stat_components"],
                          weighted_stats=True,
                          alq_sweeps=wire["alq_sweeps"])))


def test_program_checks_come_from_the_named_module(stub):
    cell = stub_cell()
    run.check_program(program_like(cell, 16), cell)
    assert stub.calls["program_checks"] == 1
    with pytest.raises(run.SetupError, match="'width': \\(8, 16\\)"):
        run.check_program(program_like(cell, 8), cell)


@pytest.mark.parametrize("name", [None, "", "no_such_arch", "wire", "adamw",
                                  "ops", "__init__", "dense_decoder.ops",
                                  "../reference/wire", "dense-decoder"])
def test_a_configuration_without_an_architecture_gives_no_result(
        tmp_path, capsys, name):
    root = str(tmp_path / "root")
    cell = tiny.make_root(root, "fp32")
    path = os.path.join(root, "bench", "configs", "tiny.json")
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "reference"}
    if name is not None:
        cfg["reference"] = name
    with open(path, "w") as f:
        json.dump(cfg, f)
    assert run.main(["--workload", cell, *ARGS], platform="cpu",
                    root=root) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "bench: no result" in out.err and "reference" in out.err


# check.follow's readings on the tiny alq cell for seed 2718281829, as the
# harness read them before the reference was looked up by name
PINNED = {
    "highest": {
        "losses": [6.6852216720581055, 6.727734565734863, 6.624087810516357],
        "first_grad": [
            4.767910480499268, 0.1310213804244995, 2.737180709838867,
            2.1551947593688965, 2.9508399963378906, 2.251107692718506,
            0.11465103924274445, 0.10985761135816574, 2.3767335414886475,
            3.4845199584960938, 2.2088146209716797, 3.6253952980041504,
            0.3081181049346924, 0.1693115085363388],
        "change": [
            0.303520530462265, 0.032776493579149246, 0.5047439336776733,
            0.954692006111145, 0.9539622068405151, 0.9523758888244629,
            0.021063033491373062, 0.02131117880344391, 0.48477327823638916,
            0.6815791130065918, 0.6834465861320496, 0.48327115178108215,
            0.04291314259171486, 0.044238485395908356],
        "levels": [
            0.0, 0.014800893142819405, 0.027453947812318802,
            0.039542056620121, 0.052653998136520386, 0.07005482912063599,
            0.10761645436286926, 1.0],
    },
    "fp8": {
        "losses": [6.6384596824646, 6.743988037109375, 6.652336597442627],
        "first_grad": [
            4.777609825134277, 0.12909437716007233, 2.7326231002807617,
            2.19354248046875, 2.952096700668335, 2.2524824142456055,
            0.1094534620642662, 0.10979399085044861, 2.308730363845825,
            3.4996578693389893, 2.154142379760742, 3.649749517440796,
            0.3104240894317627, 0.1691792905330658],
        "change": [
            0.30366241931915283, 0.03258329629898071, 0.5038744807243347,
            0.9541890025138855, 0.9540842175483704, 0.9518898725509644,
            0.021096743643283844, 0.021369216963648796, 0.48337042331695557,
            0.6812748312950134, 0.6825755834579468, 0.4828777313232422,
            0.04273335635662079, 0.04397965967655182],
        "levels": [
            0.0, 0.014789056032896042, 0.02745494619011879,
            0.039566315710544586, 0.05270743370056152, 0.07013937830924988,
            0.1077045202255249, 1.0],
    },
}


@pytest.mark.parametrize("precision", ["highest", "fp8"])
def test_readings_equal_the_pinned_ones(tmp_path, precision):
    root = str(tmp_path / "root")
    name = tiny.make_root(root, "alq")
    cell = Spec(root).cell(name)
    r = check.follow(cell.config, cell.wire, cell.job, 2718281829,
                     precision=precision)
    want = PINNED[precision]
    assert r.losses == want["losses"]
    assert r.first_grad.tolist() == want["first_grad"]
    assert r.change.tolist() == want["change"]
    assert r.levels.tolist() == want["levels"]
