"""A tiny cell for CPU tests: the program's reduced Qwen3 (2 layers,
d 256, V 512, float32 compute) under the committed wires and job, in a
benchmark root of its own."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

CONFIG = {
    "name": "tiny", "source": "https://huggingface.co/Qwen/Qwen3-0.6B",
    "reference": "dense_decoder",
    "program": {"arch": "qwen3-0.6b", "smoke": True, "use_pallas": False},
    "model_type": "qwen3", "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 1000000,
    "rms_norm_eps": 1e-05, "qk_norm": True, "attention_bias": False,
    "tie_word_embeddings": False, "param_dtype": "float32",
    "compute_dtype": "float32",
}
WIRES = {"alq": "alq3-allgather", "amq": "amq3-allgather", "fp32": "fp32"}
# a job small enough for the CPU
JOB = {"global_batch": 4, "seq_len": 16}


def make_root(path: str, wire: str = "alq", limits: dict | None = None,
              job: dict | None = None) -> str:
    """A benchmark root at ``path`` holding one cell, ``tiny.<wire>``;
    returns the cell's name."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = f"tiny.{wire}"
    bench["configs"] = [{"name": "tiny", "source": CONFIG["source"],
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"}]
    bench["workloads"] = [{"name": cell, "config": "tiny", "traffic": "t",
                           "chips": 1, "why": "CPU tests"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(path, "bench"),
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    d = os.path.join(path, "bench")
    base = json.load(open(os.path.join(d, "jobs", "b4s128-uniform.json")))
    with open(os.path.join(d, "jobs", "tiny.json"), "w") as f:
        json.dump({**base, **JOB, **(job or {})}, f)
    with open(os.path.join(d, "configs", "tiny.json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(d, "traffic", "t.json"), "w") as f:
        json.dump({"wire": WIRES[wire], "job": "tiny"}, f)
    quantized = wire != "fp32"
    lim = {"loss_gap_0": 1e-3, "loss_gap_1": 1e-3, "loss_gap_2": 1e-3,
           "grad_gap": 0.2, "change_gap": 0.2}
    lim.update({"levels_gap": 1e-3, "agg_err_gap": 0.2} if quantized
               else {"agg_rel_err": 1e-3})
    lim.update(limits or {})
    with open(os.path.join(d, "limits", f"{cell}.json"), "w") as f:
        json.dump(lim, f)
    return cell
