"""Serving launcher: batched prefill + decode of a (smoke) model on the
local mesh — the same serve_step the decode dry-run shapes lower.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --prompt-len 32 --gen 16 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_axes
from repro.models.transformer import Model
from repro.serve.engine import ServeConfig, make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = make_local_mesh(tp=args.tp)
    data_axes, model_axis = mesh_axes(mesh)
    tp = mesh.shape[model_axis]
    model = Model(cfg, tp=tp, dp=mesh.size // tp, data_axes=data_axes)
    max_len = args.prompt_len + args.gen
    scfg = ServeConfig(max_len=max_len)
    cache_shards = tp
    prefill = make_prefill_step(model, scfg, cache_shards=cache_shards)
    decode = make_decode_step(model, scfg, cache_shards=cache_shards)

    pspecs = model.param_specs()
    bspec = P(data_axes)
    cspecs = model.cache_pspecs(data_axes)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        pf = jax.jit(jax.shard_map(
            lambda p, i: prefill(p, i), in_specs=(pspecs, bspec),
            out_specs=(bspec, cspecs), check_vma=False))
        df = jax.jit(jax.shard_map(
            lambda p, t, pos, c: decode(p, t, pos, c),
            in_specs=(pspecs, bspec, bspec, cspecs),
            out_specs=(bspec, cspecs), check_vma=False))

        ids = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
        t0 = time.time()
        tok, caches = pf(params, ids)
        print(f"prefill({args.batch}x{args.prompt_len}) "
              f"{(time.time()-t0)*1e3:.0f} ms -> first tokens "
              f"{np.asarray(tok)}")
        out = [np.asarray(tok)]
        t0 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
            tok, caches = df(params, tok, pos, caches)
            out.append(np.asarray(tok))
        dt = time.time() - t0
        gen = np.stack(out, 1)
        print(f"decoded {args.gen - 1} steps in {dt*1e3:.0f} ms "
              f"({dt/(args.gen-1)*1e3:.1f} ms/tok)")
        for b in range(min(args.batch, 2)):
            print(f"  seq[{b}]: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
