"""A whole run of the harness on the CPU at a tiny size: sound, it comes
out correct; with the timed path broken underneath, not correct."""
import json
import os
import subprocess
import sys

import pytest

import tiny
from tiny import ROOT

from bench import run
from repro.models.transformer import Model
from repro.train import train_step

ARGS = ["--seed", "3000000017", "--seconds", "0.5", "--trace", "0"]


def frozen(monkeypatch):
    """The step returns the parameters and optimizer state unchanged."""
    monkeypatch.setattr(train_step, "apply_updates",
                        lambda cfg, params, grads, state: (params, state))


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    loss = Model.loss

    def half(self, params, batch, sync_ctx=None):
        return loss(self, params, {k: v[: v.shape[0] // 2]
                                   for k, v in batch.items()}, sync_ctx)

    monkeypatch.setattr(Model, "loss", half)


def no_exchange(monkeypatch):
    """The gradient goes on without passing the wire."""
    sync = train_step.compressed_allreduce

    def skip(flat, *args, **kwargs):
        _, state, metrics = sync(flat, *args, **kwargs)
        return flat, state, metrics

    monkeypatch.setattr(train_step, "compressed_allreduce", skip)


def result(tmp_path, capsys, wire):
    root = str(tmp_path / "root")
    cell = tiny.make_root(root, wire)
    assert run.main(["--workload", cell, *ARGS], platform="cpu",
                    root=root) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("wire", ["alq", "fp32"])
def test_sound_run_is_correct(tmp_path, capsys, wire):
    r = result(tmp_path, capsys, wire)
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("wire,fault", [
    ("alq", frozen), ("alq", half_batch), ("alq", no_exchange),
    ("fp32", frozen), ("fp32", half_batch)])
def test_broken_step_is_not_correct(tmp_path, capsys, monkeypatch, wire,
                                    fault):
    fault(monkeypatch)
    assert result(tmp_path, capsys, wire)["correct"] is False


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-0.6b.fp32", *ARGS], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-0.6b.fp32", *ARGS], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
