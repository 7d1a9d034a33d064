"""The trace reduction on hand-made events and on a small recorded
trace of a chip run."""
import gzip
import json
import os

import pytest

from bench import tracereduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    # a 100 ns window from 5 ns; ops at [10, 30), [20, 40)
    # (overlapping) and [60, 70) on one device; one op straddles the
    # window's end and one lies before it
    return {
        "device": {"/device:TPU:0": [
            ["fusion.1", 10.0, 20.0], ["quantize.3", 20.0, 20.0],
            ["dequantize", 60.0, 10.0], ["fusion.1", 95.0, 20.0],
            ["before", 0.0, 5.0]]},
        "host": [["window", 5.0, 100.0], ["dispatch", 5.0, 4.0],
                 ["wait", 41.0, 19.0], ["batch", 72.0, 20.0]],
    }


def test_busy_union_and_window():
    s = tracereduce.summarize(synthetic())
    # the window is [5, 105); busy: [10, 40) + [60, 70) + [95, 105)
    assert s["busy_s"] == pytest.approx(50e-9)
    assert s["window_s"] == pytest.approx(100e-9)


def test_idle_gaps_named_by_host_spans():
    s = tracereduce.summarize(synthetic())
    # gaps [5, 10) dispatch, [40, 60) wait, [70, 95) batch
    assert s["idle_gaps"] == [["batch", pytest.approx(25e-9)],
                              ["wait", pytest.approx(20e-9)],
                              ["dispatch", pytest.approx(5e-9)]]


def test_ops_and_kernel_times():
    s = tracereduce.summarize(synthetic())
    # 20 ns, and 10 of the one that straddles the window's end
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert tracereduce.kernel_time(s, "quantize") == (
        pytest.approx(20e-9), 1)
    assert tracereduce.kernel_time(s, "dequantize") == (
        pytest.approx(10e-9), 1)


def test_no_window_is_an_error():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracereduce.summarize(ev)


def recorded():
    with gzip.open(os.path.join(DATA, "trace_qwen3_alq3.json.gz"), "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """One window of qwen3-0.6b.alq3.allgather on a TPU v5 lite (one
    28.7 s step): the values the chip run reported from it."""
    s = tracereduce.summarize(recorded())
    assert s["window_s"] == pytest.approx(28.716984163)
    assert s["busy_s"] == pytest.approx(28.714408088)
    idle = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    assert idle == pytest.approx(0.008970562456622755, rel=1e-9)
    # one call of each kernel per step, found by their own names
    assert tracereduce.kernel_time(s, "quantize") == (
        pytest.approx(0.021614858), 1)
    assert tracereduce.kernel_time(s, "dequantize") == (
        pytest.approx(0.007056642), 1)
    # the packer's gathers and scatters take the step
    top = [n.split(" ")[0] for n, _ in s["device_ops"][:4]]
    assert top == ["fusion.8", "fusion.9", "fusion.14", "fusion.15"]
    assert sum(sec for _, sec in s["device_ops"][:4]) > 0.99 * s["busy_s"]
    assert [d for d, _ in s["idle_gaps"][:2]] == ["wait", "dispatch"]
