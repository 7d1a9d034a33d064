"""BENCHMARK.json is well formed, and every name in it resolves to a
file under bench/."""
import json
import os
import re

import pytest

from conftest import ROOT

from bench.spec import NAME, UNIT, Spec

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
# the numbers bench/check.py reads; a cell's limits compare some of them
COMMON = {"loss_gap_0", "loss_gap_1", "loss_gap_2", "grad_gap", "change_gap",
          "grad_gap_total"}
QUANTIZED_NUMBERS = COMMON | {"levels_gap", "agg_err_gap"}
FP32_NUMBERS = COMMON | {"agg_rel_err"}
# keys that name a width, which a configuration never cuts
WIDTH = re.compile(r"(_dim|_rank)$|_size$|intermediate|latent|d_state|"
                   r"expand|expansion|experts_per_tok")


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(one_line(w) for w in BENCH["command"])
    for w in BENCH["command"][1:]:
        assert not w.startswith("/") and ".." not in w.split("/")
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_have_their_keys_and_names(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY_KEYS[section] <= set(e) <= ENTRY_KEYS[section] | extra
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert one_line(e[k]), (e["name"], k)


def test_configs_resolve_and_state_their_cuts():
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        assert set(cfg["published"]) == set(c["reduced"])
        assert all(cfg[k] != cfg["published"][k] for k in c["reduced"])


def test_every_cell_resolves_to_its_files():
    spec = Spec(ROOT)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    pairs = set()
    four = 0
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        pairs.add((w["config"], w["traffic"]))
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        cell = spec.cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        want = QUANTIZED_NUMBERS if cell.quantized else FP32_NUMBERS
        assert cell.limits and set(cell.limits) <= want
        assert all(0 < v for v in cell.limits.values())
    assert len(pairs) == len(BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert os.path.isfile(spec.metric_path(m["name"]))
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in BENCH["workloads"]}


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"launcher", "train step", "level update", "encode",
                      "collective", "decode + mean", "optimizer", "device"}
