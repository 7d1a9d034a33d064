"""The yardstick's counts against values worked by hand."""
import json
import os

import pytest

from conftest import ROOT

from bench import yardstick


def config(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs",
                                       f"{name}.json")))


# hand-worked: per layer wq + wk + wv + wo + 3 FFN matrices + 2 norms
# (+ 2 qk norms for Qwen3), times the layers, plus embedding, LM head and
# the final norm
@pytest.mark.parametrize("name,coords", [
    ("qwen3-0.6b", 9 * (1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024
                        + 3 * 1024 * 3072 + 2 * 1024 + 2 * 128)
     + 2 * 151936 * 1024 + 1024),
    ("granite-3-2b", 4 * 60821504 + 2 * 100669440 + 2048),
])
def test_num_coords(name, coords):
    assert yardstick.num_coords(config(name)) == coords


def test_coords_as_the_issue_counts_them():
    assert yardstick.num_coords(config("qwen3-0.6b")) == 452_744_448
    assert yardstick.num_coords(config("granite-3-2b")) == 444_626_944


@pytest.mark.parametrize("name,flops", [
    # 3 x (2 x (9 x 15,728,640 + 1024 x 151,936) + 9 x 4 x 2048 x 64.5)
    ("qwen3-0.6b", 1_797_107_712.0),
    # 3 x (2 x (4 x 60,817,408 + 2048 x 49,155) + 4 x 4 x 2048 x 64.5)
    ("granite-3-2b", 2_069_975_040.0),
])
def test_model_flops_per_token(name, flops):
    assert yardstick.model_flops_per_token(config(name), 128) == flops


def test_kernel_work_qwen3():
    n = 452_744_448
    # 442,134 buckets of 1024 (the last one partial), one f32 norm each
    nb = 442_134
    assert yardstick.kernel_work("quantize", n, 1024, 8) == (
        nb * 1024 * (4 + 4 + 1) + 4 * nb, nb * 1024 * (18 + 3 * 6))
    assert yardstick.kernel_work("dequantize", n, 1024, 8) == (
        nb * 1024 * (1 + 4) + 4 * nb, nb * 1024 * (2 + 2 * 7 + 4 + 2))
    # 256 levels take 2-byte codes
    assert yardstick.kernel_work("dequantize", 1024, 1024, 256)[0] == \
        1024 * 6 + 4


def test_roofline_pct():
    # 819 GB in 2 s at 819 GB/s: half the roofline, bound by bytes
    assert yardstick.roofline_pct(819e9, 1.0, 2.0, "TPU v5 lite") == (
        pytest.approx(50.0), "bytes")
    # 197 TFLOP in 4 s: a quarter, bound by operations
    assert yardstick.roofline_pct(1.0, 197e12, 4.0, "TPU v5 lite") == (
        pytest.approx(25.0), "ops")


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError):
        yardstick.peaks("cpu")
