"""Device time by layer (``bench/scopes.py``) on a hand-written module,
on synthetic summaries, and on a recorded chip run."""
import gzip
import json
import os
import types

import pytest

from bench import scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "metrics")

HLO = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(u32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: u32[8]) -> f32[8] {
  %param_0.1 = u32[8]{0} parameter(0)
  %convert.1 = f32[8]{0} convert(%param_0.1), metadata={op_name="jit(step)/encode/convert_element_type"}
  ROOT %multiply.1 = f32[8]{0} multiply(%convert.1, %convert.1), metadata={op_name="jit(step)/decode/mul" stack_frame_id=3}
}

%body.2 (p.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.2 = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = s32[] get-tuple-element(%p.2), index=0
  %gte.4 = f32[8]{0} get-tuple-element(%p.2), index=1
  %dot.5 = f32[8]{0} multiply(%gte.4, %gte.4), metadata={op_name="jit(step)/fwd_bwd/while/body/dot_general"}
  %constant.6 = s32[] constant(1)
  %add.7 = s32[] add(%gte.3, %constant.6), metadata={op_name="jit(step)/fwd_bwd/while/body/add"}
  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(%add.7, %dot.5)
}

%cond.9 (p.9: (s32[], f32[8])) -> pred[] {
  %p.9 = (s32[], f32[8]{0}) parameter(0)
  %gte.10 = s32[] get-tuple-element(%p.9), index=0
  %constant.11 = s32[] constant(4)
  ROOT %lt.12 = pred[] compare(%gte.10, %constant.11), direction=LT, metadata={op_name="jit(step)/fwd_bwd/while/cond/lt"}
}

%fused_computation.30 (param_0.30: f32[8]) -> f32[16] {
  %param_0.30 = f32[8]{0} parameter(0)
  %reshape.31 = f32[8]{0} reshape(%param_0.30), metadata={op_name="jit(step)/ravel/reshape"}
  ROOT %concatenate.32 = f32[16]{0} concatenate(%reshape.31, %reshape.31), dimensions={0}
}

ENTRY %main.20 (words.1: u32[8]) -> f32[16] {
  %words.1 = u32[8]{0} parameter(0), metadata={op_name="words"}
  %copy.21 = u32[8]{0} copy(%words.1)
  %xor.22 = u32[8]{0} xor(%words.1, %words.1), metadata={op_name="jit(step)/jit(_threefry_fold_in)/xor"}
  %fusion.8 = f32[8]{0} fusion(%copy.21), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/decode/mul" stack_frame_id=3}
  %multiply.13 = f32[8]{0} multiply(%fusion.8, %fusion.8), metadata={op_name="jit(step)/fwd_bwd/transpose(jvp(jit(loss)))/mul"}
  %copy.14 = f32[8]{0} copy(%multiply.13)
  %constant.15 = s32[] constant(0)
  %tuple.16 = (s32[], f32[8]{0}) tuple(%constant.15, %copy.14)
  %while.17 = (s32[], f32[8]{0}) while(%tuple.16), condition=%cond.9, body=%body.2, metadata={op_name="jit(step)/fwd_bwd/while"}
  %gte.18 = f32[8]{0} get-tuple-element(%while.17), index=1
  %add.19 = f32[8]{0} add(%gte.18, %gte.18), metadata={op_name="jit(step)/encode/decode/add"}
  ROOT %fusion.33 = f32[16]{0} fusion(%add.19), kind=kLoop, calls=%fused_computation.30
}

"""


def test_layer_of_takes_the_first_layer_segment():
    assert scopes.layer_of("jit(step)/decode/mul") == "decode"
    assert scopes.layer_of("jit(step)/fwd_bwd/transpose(jvp())/mul") \
        == "fwd_bwd"
    assert scopes.layer_of("jit(step)/encode/decode/add") == "encode"
    # a segment only counts whole
    assert scopes.layer_of("jit(step)/jit(decode)/mul") is None
    assert scopes.layer_of("jit(step)/mul") is None


def test_parse_reads_each_instruction_s_layer():
    m = scopes.parse(HLO)
    # a fusion carries its root's scope
    assert m.layer["fusion.8"] == "decode"
    # a backward op under transpose(jvp(...)) of fwd_bwd
    assert m.layer["multiply.13"] == "fwd_bwd"
    # metadata without a layer
    assert m.layer["words.1"] is None
    assert m.layer["xor.22"] is None
    # a while and the ops of its body
    assert m.layer["while.17"] == "fwd_bwd"
    assert m.layer["dot.5"] == m.layer["add.7"] == "fwd_bwd"
    assert m.layer["add.19"] == "encode"
    assert m.calls["while.17"] == ("cond.9", "body.2")
    assert m.calls["fusion.8"] == ("fused_computation.1",)
    assert m.calls["fusion.33"] == ("fused_computation.30",)
    assert m.body["body.2"][-1] == "tuple.8"
    assert m.scoped()


def test_instructions_without_op_name_take_a_layer_of_their_data():
    m = scopes.parse(HLO)
    # a fusion whose root has none: the layer its fused instructions share
    assert m.layer["fusion.33"] == "ravel"
    # a copy: the layer of its operand
    assert m.layer["copy.14"] == "fwd_bwd"
    # operands of no layer: the layer of its users
    assert m.layer["copy.21"] == "decode"
    # a tuple between a layer's copy and its while
    assert m.layer["tuple.16"] == "fwd_bwd"


def test_nested_ops_are_those_of_the_body():
    m = scopes.parse(HLO)
    present = ["fusion.8", "multiply.13", "copy.14", "while.17", "dot.5",
               "add.7", "lt.12", "add.19", "fusion.33"]
    assert m.nested(present) == {"while.17": ["add.7", "dot.5", "lt.12"]}


# the module of a program without the layer scopes
PLAIN = HLO.replace("/encode/", "/").replace("/decode/", "/").replace(
    "/fwd_bwd/", "/").replace("/ravel/", "/")


def test_a_program_without_scopes_has_none():
    m = scopes.parse(PLAIN)
    assert not m.scoped()
    assert m.layer["fusion.8"] is None


def summary():
    # a 100 ns window on one device: the while spans its body's ops four
    # times (4 x (3 + 2 + 1) = 24 ns of 30), the rest one after another
    ops = {"fusion.8 f32[8]": 20e-9, "multiply.13 f32[8]": 10e-9,
           "copy.14 f32[8]": 5e-9, "while.17 (s32[], f32[8])": 30e-9,
           "dot.5 f32[8]": 12e-9, "add.7 s32[]": 8e-9, "lt.12 pred[]": 4e-9,
           "add.19 f32[8]": 15e-9, "xor.22 u32[8]": 3e-9,
           "fusion.33 f32[16]": 7e-9}
    busy = 20e-9 + 10e-9 + 5e-9 + 30e-9 + 15e-9 + 3e-9 + 7e-9
    return {"op_seconds": ops, "busy_s": busy, "window_s": 100e-9}


def test_layer_seconds_count_each_interval_once():
    m = scopes.parse(HLO)
    s = summary()
    ops = scopes.op_seconds_by_name(s)
    got = scopes.layer_seconds(ops, m.layer, m.nested(ops))
    assert got == {
        "decode": pytest.approx(20e-9),
        # the transpose op, its copy, the while's own 6 ns and its
        # body's 24
        "fwd_bwd": pytest.approx(10e-9 + 5e-9 + 6e-9 + 24e-9),
        None: pytest.approx(3e-9),
        "encode": pytest.approx(15e-9),
        "ravel": pytest.approx(7e-9)}
    # the layers and the unscoped rest partition the busy time
    assert sum(got.values()) == pytest.approx(s["busy_s"])


def test_a_container_never_reads_below_zero():
    m = scopes.parse(HLO)
    ops = {"while.17": 1e-9, "dot.5": 12e-9}
    got = scopes.layer_seconds(ops, m.layer, m.nested(ops))
    assert got == {"fwd_bwd": pytest.approx(12e-9)}


def read(metric, run):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + metric, os.path.join(METRICS, metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def fake_run(text, trace, steps=2, chips=1):
    return types.SimpleNamespace(
        compiled=types.SimpleNamespace(as_text=lambda: text), trace=trace,
        steps=steps, chips=chips)


def test_readers_give_ms_per_step_and_the_unscoped_share():
    run = fake_run(HLO, summary(), steps=2)
    assert read("decode_ms", run) == pytest.approx(1000 * 20e-9 / 2)
    assert read("fwd_bwd_ms", run) == pytest.approx(1000 * 45e-9 / 2)
    assert read("encode_ms", run) == pytest.approx(1000 * 15e-9 / 2)
    # a layer with no operation in the window reads 0
    assert read("optimizer_ms", run) == 0.0
    assert read("ravel_ms", run) == pytest.approx(1000 * 7e-9 / 2)
    assert read("unscoped_device_pct", run) == pytest.approx(
        100 * 3e-9 / summary()["busy_s"])
    # on two chips the trace holds each chip's operations
    two = summary()
    two["op_seconds"] = {k: 2 * v for k, v in two["op_seconds"].items()}
    assert read("decode_ms", fake_run(HLO, two, chips=2)) == pytest.approx(
        1000 * 20e-9 / 2)


NAMES = ["fwd_bwd_ms", "ravel_ms", "step_metrics_ms", "optimizer_ms",
         "encode_ms", "decode_ms", "unscoped_device_pct"]


@pytest.mark.parametrize("metric", NAMES)
def test_readers_are_silent_untraced_or_without_scopes(metric):
    assert read(metric, fake_run(HLO, None)) is None
    assert read(metric, fake_run(PLAIN, summary())) is None


def recorded():
    with gzip.open(os.path.join(DATA, "scopes_qwen3_alq3.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_chip_run_is_encode_and_decode():
    """One traced window of qwen3-0.6b.alq3.allgather on a TPU v5 lite:
    each device operation with its layer and the operations nested in
    it, as the chip run's compiled step gave them."""
    rec = recorded()
    got = scopes.layer_seconds(rec["op_seconds"], rec["layer"],
                               rec["nested"])
    busy = rec["busy_s"]
    assert sum(got.values()) == pytest.approx(busy, rel=0.01)
    assert got.get("encode", 0) + got.get("decode", 0) >= 0.98 * busy
    assert got.get(None, 0) < 0.02 * busy
