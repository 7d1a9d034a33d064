"""chip_smoke.py: refuses any platform but a TPU before a phase runs,
and its phases run end to end at a tiny size when a test lets it accept
the CPU (kernels interpreted, so no tpu_custom_call is expected)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
TINY = ["--arch", "qwen3-0.6b", "--smoke", "--scheme", "alq", "--bits",
        "3", "--bucket", "1024", "--steps", "4", "--batch", "4", "--seq",
        "32", "--data", "uniform", "--update-at", "2", "--use-pallas"]


def _run(cmd, cwd, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(cmd, cwd=cwd, env=e, capture_output=True,
                          text=True, timeout=600)


def _last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("alone", [False, True])
def test_exits_nonzero_without_tpu(tmp_path, alone):
    """On the CPU, and in a directory holding chip_smoke.py and nothing
    else of the repository, it fails without printing a result."""
    cwd = ROOT
    if alone:
        shutil.copy(SMOKE, tmp_path)
        cwd = str(tmp_path)
    proc = _run([sys.executable, "chip_smoke.py"], cwd,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if not alone:
        assert "no phase ran" in proc.stderr


def test_one_chip_phases_on_cpu(monkeypatch, capsys, tmp_path):
    # with the variable set, chip_smoke leaves the cache to jax, which
    # read its environment before this test set it: nothing is cached
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    assert chip_smoke.main([], platform="cpu", train_args=TINY) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert "code_mismatches=0" in out
    assert "sync all_gather M=1" in out


def test_four_chip_phase_on_fake_devices(tmp_path):
    body = ("import chip_smoke, sys; sys.exit(chip_smoke.main("
            f"['--chips', '4'], platform='cpu', train_args={TINY!r}))")
    proc = _run([sys.executable, "-c", body], ROOT,
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert _last_json(proc.stdout)["device"]["count"] == 4
    assert "dp4 two_phase step0 loss" in proc.stdout
    assert "sync two_phase M=4" in proc.stdout
