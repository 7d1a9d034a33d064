#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 \\
        [--variants self,fp8,half_batch,no_exchange,frozen]

For each seed the plain reference follows the cell's checked steps, and
then each variant follows them in the program's place and is compared
with it by the numbers of ``bench/check.py``:

    self         the reference itself with another rounding draw: the
                 floor that independent rounding alone reads
    fp8          the control, the reference with every matrix product in
                 float8 e4m3, the precision below the configuration's
                 bfloat16; it has to read as not correct
    half_batch   a planted fault: the gradient of half of the batch
    no_exchange  a planted fault: the gradient skips the wire
    frozen       a planted fault: the step leaves the parameters as
                 they were

Prints one JSON line per seed and variant.  The benchmark's own runs do
not run this.  Exits non-zero on any platform but a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

VARIANTS = {"self": {}, "fp8": {"precision": "fp8"},
            "half_batch": {"fault": "half_batch"},
            "no_exchange": {"fault": "no_exchange"},
            "frozen": {"fault": "frozen"}}


def readings(cell, seeds, variants):
    from bench import check, generator as gen
    for seed in seeds:
        ref = check.follow(cell.config, cell.wire, cell.job, seed)
        for name in variants:
            if name == "no_exchange" and not cell.quantized:
                continue
            got = check.follow(cell.config, cell.wire, cell.job, seed,
                               stream=gen.CANDIDATE, reference=False,
                               **VARIANTS[name])
            nums = check.numbers(got, ref, cell.quantized)
            del got
            yield {"cell": cell.name, "seed": seed, "variant": name,
                   "numbers": nums,
                   "fails": sorted(k for k, v in cell.limits.items()
                                   if not nums[k] <= v)}
        del ref


def main(argv=None, *, platform: str = "tpu", root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    opts = ap.parse_args(argv)
    import jax
    from bench.spec import Spec
    if jax.devices()[0].platform != platform:
        print(f"controls: needs a {platform} device", file=sys.stderr)
        return 2
    if platform == "tpu":
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = Spec(root).cell(opts.workload)
    seeds = [int(s) for s in opts.seeds.split(",")]
    for line in readings(cell, seeds, opts.variants.split(",")):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
