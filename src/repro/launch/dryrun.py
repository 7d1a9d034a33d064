import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers and compiles on the production mesh, and extract the
roofline terms from the compiled artifact.

The two lines above MUST precede any other import (jax locks the device
count at first init); do not set that flag globally — smoke tests and
benchmarks should see one device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --sync all_gather --out experiments/dryrun
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, input_specs
from repro.core.schemes import QuantScheme
from repro.launch import hlo_analysis, jaxpr_cost
from repro.launch.mesh import (
    PRODUCTION_DEVICE_KIND, make_production_mesh, mesh_axes)
from repro.models.transformer import Model
from repro.train.optim import OptimConfig
from repro.train.train_step import (
    TrainConfig, TrainState, init_train_state, make_train_step)

# archs whose long_500k is skipped (pure full-attention; DESIGN.md §4)
LONG_SKIP = {
    "qwen1.5-32b", "qwen3-0.6b", "granite-3-2b", "llama3.2-1b",
    "llama-3.2-vision-11b", "musicgen-large",
}


FSDP_BYTES_THRESHOLD = 6e9  # per-device params(+opt) budget before FSDP
ACTIVATION_BUDGET = 8e9     # per-device activation bytes before microbatching


def auto_microbatches(cfg, shape, mesh) -> int:
    """Smallest power-of-two microbatch count whose per-device activation
    estimate (~3 x layers x B_micro x S x d bf16, the scan-carry residuals
    plus in-layer bwd transients) fits the budget."""
    data_axes, model_axis = mesh_axes(mesh)
    dp = 1
    for ax in data_axes:
        dp *= mesh.shape[ax]
    b_local = max(shape.global_batch // dp, 1)
    micro = 1
    while micro < b_local:
        b_micro = b_local // micro
        est = 3.0 * cfg.num_layers * b_micro * shape.seq_len * cfg.d_model * 2
        if est <= ACTIVATION_BUDGET:
            break
        micro *= 2
    return micro


def build_model(cfg, mesh, shape, scheme=None, sync_mode="all_gather"):
    data_axes, model_axis = mesh_axes(mesh)
    tp = mesh.shape[model_axis]
    dp = 1
    for ax in data_axes:
        dp *= mesh.shape[ax]
    if shape.kind == "decode" and shape.global_batch < dp:
        # batch-1 long-context: shard the cache sequence over everything
        seq_axes = tuple(data_axes) + (model_axis,)
        batch_axes = ()
    else:
        seq_axes = (model_axis,)
        batch_axes = tuple(data_axes)
    # params(+grads+momentum) per device under DP replication:
    n = cfg.param_count()
    per_dev = n * (12 if shape.kind == "train" else 4) / tp
    param_mode = "fsdp" if per_dev > FSDP_BYTES_THRESHOLD else "dp"
    fsdp_sync = ("quantized" if shape.kind == "train"
                 and sync_mode != "fp32" else "fp32")
    model = Model(cfg, tp=tp, dp=dp, data_axes=data_axes,
                  seq_shard_axes=seq_axes, param_mode=param_mode,
                  fsdp_scheme=scheme, fsdp_sync=fsdp_sync)
    return model, batch_axes, data_axes


def lower_pair(cfg, shape, mesh, *, sync_mode="all_gather",
               scheme_name="alq", bits=3, bucket=8192,
               microbatches=1, remat="full"):
    """Lower + compile one (arch, shape, mesh) combination.

    Returns (compiled, jaxpr_cost, lower_seconds, compile_seconds).
    """
    scheme = QuantScheme(name=scheme_name, bits=bits, bucket_size=bucket)
    model, batch_axes, data_axes = build_model(cfg, mesh, shape, scheme,
                                               sync_mode)
    model.remat = remat
    pspecs = model.param_specs()
    pstruct = model.param_struct()
    specs = input_specs(cfg, shape)
    bspec = P(batch_axes) if batch_axes else P()

    if shape.kind == "train":
        # use_pallas=False: on CPU the Pallas kernels run in interpret
        # mode, which materializes every grid block at once — fine for
        # kernel tests, wrong for memory analysis.  On real TPU the
        # compiled pallas_call path is enabled (launch/train.py).
        tcfg = TrainConfig(scheme=scheme, optim=OptimConfig(name="sgdm"),
                           sync_mode=sync_mode, microbatches=microbatches,
                           use_pallas=False)
        step = make_train_step(model, tcfg, data_axes=data_axes)
        state_struct = jax.eval_shape(
            lambda: init_train_state(model, tcfg, jax.random.PRNGKey(0)))
        state_specs = TrainState(
            params=pspecs,
            opt=type(state_struct.opt)(
                mu=pspecs,
                nu=None if state_struct.opt.nu is None else pspecs,
                count=P()),
            scheme_state=jax.tree.map(lambda _: P(),
                                      state_struct.scheme_state),
            step=P(), rng=P())
        batch_specs = {k: bspec for k in specs}

        def fn(state, batch):
            return step(state, batch)

        from repro.train.train_step import metric_specs
        smapped = jax.shard_map(
            fn, mesh=mesh, in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, metric_specs()),
            check_vma=False)
        args = (state_struct, specs)

    elif shape.kind == "prefill":
        cache_shards = model.tp
        cspecs = model.cache_pspecs(batch_axes)
        cstruct = model.global_cache_struct(
            shape.global_batch, shape.seq_len, cache_shards)

        def fn(params, batch):
            return model.prefill(params, batch["ids"],
                                 batch.get("vision"),
                                 max_len=shape.seq_len,
                                 cache_shards=cache_shards)

        smapped = jax.shard_map(
            fn, mesh=mesh, in_specs=(pspecs, {k: bspec for k in specs}),
            out_specs=(bspec, cspecs), check_vma=False)
        args = (pstruct, specs)

    else:  # decode
        cache_shards = 1
        for ax in model.seq_shard_axes:
            cache_shards *= mesh.shape[ax]
        cspecs = model.cache_pspecs(batch_axes)
        cstruct = model.global_cache_struct(
            shape.global_batch, shape.seq_len, cache_shards)
        vision_struct = specs.pop("vision", None)

        def fn(params, token, pos, caches):
            logits, new_caches = model.decode(
                params, token, pos, caches, None,
                cache_shards=cache_shards)
            return jnp.argmax(logits, -1).astype(jnp.int32), new_caches

        smapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(pspecs, bspec, bspec, cspecs),
            out_specs=(bspec, cspecs), check_vma=False)
        args = (pstruct, specs["token"], specs["pos"], cstruct)

    with jax.set_mesh(mesh):
        t0 = time.time()
        acost = jaxpr_cost.analyze_fn(smapped, *args)
        lowered = jax.jit(smapped).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    return compiled, acost, t1 - t0, t2 - t1


def run_one(arch, shape_name, mesh_kind, *, sync_mode, out_dir,
            scheme_name="alq", bits=3, tag="", microbatches=1,
            remat="full"):
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "sync": sync_mode, "scheme": scheme_name, "bits": bits,
        "chips": mesh.size, "tag": tag, "microbatches": microbatches,
        "remat": remat,
    }
    if microbatches == 0 and SHAPES[shape_name].kind == "train":
        microbatches = auto_microbatches(cfg, SHAPES[shape_name], mesh)
        rec["microbatches"] = microbatches
    try:
        compiled, acost, t_low, t_comp = lower_pair(
            cfg, shape, mesh, sync_mode=sync_mode,
            scheme_name=scheme_name, bits=bits,
            microbatches=microbatches, remat=remat)
        mem = compiled.memory_analysis()
        hlo_roof = hlo_analysis.analyze(compiled, PRODUCTION_DEVICE_KIND)
        # primary roofline terms from the jaxpr walker (scan-exact);
        # compiled cost_analysis kept as a secondary record
        roof = hlo_analysis.Roofline(
            flops_per_device=acost.flops,
            hbm_bytes_per_device=acost.hbm_bytes,
            collective_wire_bytes=acost.collective_bytes,
            bytes_by_kind=acost.by_collective,
            peaks=hlo_roof.peaks,
        )
        # model flops: 6*N_active*D for training, 2*N_active*D prefill,
        # 2*N_active*B decode
        n_act = cfg.active_param_count()
        shp = SHAPES[shape_name]
        tokens = shp.global_batch * (shp.seq_len if shp.kind != "decode"
                                     else 1)
        mult = 6 if shp.kind == "train" else 2
        model_flops_per_dev = mult * n_act * tokens / mesh.size
        rec.update({
            "ok": True,
            "lower_s": round(t_low, 2),
            "compile_s": round(t_comp, 2),
            "bytes_per_device": {
                "argument": mem.argument_size_in_bytes,
                "output": mem.output_size_in_bytes,
                "temp": mem.temp_size_in_bytes,
                "total": (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes),
            },
            "roofline": roof.to_dict(),
            "model_flops_per_device": model_flops_per_dev,
            "useful_flops_ratio": (model_flops_per_dev
                                   / max(roof.flops_per_device, 1.0)),
            "hlo_cost_analysis": hlo_roof.to_dict(),
        })
        print(f"[OK] {arch} x {shape_name} x {mesh_kind}"
              f" flops/dev={roof.flops_per_device:.3e}"
              f" wire={roof.collective_wire_bytes:.3e}B"
              f" dom={roof.dominant}"
              f" useful={rec['useful_flops_ratio']:.2f}"
              f" mem={rec['bytes_per_device']['total']/2**30:.1f}GiB"
              f" (lower {t_low:.0f}s compile {t_comp:.0f}s)")
    except Exception as e:  # record failures — they are bugs to fix
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:]})
        print(f"[FAIL] {arch} x {shape_name} x {mesh_kind}: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fn = os.path.join(
            out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--sync", default="all_gather",
                    choices=["fp32", "all_gather", "two_phase"])
    ap.add_argument("--scheme", default="alq")
    ap.add_argument("--bits", type=int, default=3)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--micro", type=int, default=0,
                    help="microbatches per step; 0 = auto-size")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "psum", "none"])
    args = ap.parse_args()

    archs = configs.ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        cfg = configs.get_config(arch)
        for shape_name in shapes:
            if shape_name == "long_500k" and arch in LONG_SKIP:
                print(f"[SKIP] {arch} x long_500k (pure full attention; "
                      "DESIGN.md §4)")
                continue
            for mesh_kind in meshes:
                results.append(run_one(
                    arch, shape_name, mesh_kind, sync_mode=args.sync,
                    out_dir=args.out, scheme_name=args.scheme,
                    bits=args.bits, tag=args.tag,
                    microbatches=args.micro, remat=args.remat))
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} combinations compiled")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
