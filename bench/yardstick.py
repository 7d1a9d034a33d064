"""Peaks of the chip and the work of the model and of the kernels,
counted from shapes.

``PEAKS`` is copied from ``repro.launch.hlo_analysis`` so that the
program cannot move it.  Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 819 GB/s HBM per chip.
"""
from __future__ import annotations

import math

import jax

from bench import reference

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source")
    return PEAKS[device_kind]


def num_coords(cfg: dict) -> int:
    """Gradient coordinates: every weight of the model."""
    shapes = reference.of(cfg).param_shapes(cfg)
    return sum(math.prod(s) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of the configuration's
    architecture, without recomputation."""
    return reference.of(cfg).flops_per_token(cfg, seq_len)


# per coordinate: bytes moved and vector operations, for 2**bits levels
def _quantize_cost(levels: int) -> tuple[int, int]:
    code = 1 if levels <= 128 else 2
    # read v (f32) and u (f32), write the code; ops: square-add for the
    # norm, abs, divide, clip twice, (compare, two selects) per inner
    # level, the rounding step (two subtractions, max, divide), compare
    # and add, the sign (two compares, two selects), multiply, convert
    return 4 + 4 + code, 18 + 3 * (levels - 2)


def _dequantize_cost(levels: int) -> tuple[int, int]:
    code = 1 if levels <= 128 else 2
    # read the code, write f32; ops: convert, abs, (compare, select) per
    # nonzero level, the sign (two compares, two selects), two multiplies
    return code + 4, 2 + 2 * (levels - 1) + 4 + 2


KERNELS = {"quantize": _quantize_cost, "dequantize": _dequantize_cost}


def kernel_work(kernel: str, n: int, bucket: int, levels: int
                ) -> tuple[float, float]:
    """(bytes, ops) of one call of ``kernel`` over ``n`` coordinates in
    buckets of ``bucket``, each with one f32 norm read or written."""
    nb = -(-n // bucket)
    per_byte, per_op = KERNELS[kernel](levels)
    return float(nb * bucket * per_byte + 4 * nb), float(nb * bucket * per_op)


def roofline_pct(bytes_: float, ops: float, seconds: float,
                 device_kind: str) -> tuple[float, str]:
    """Least time the chip could take over the time taken, in %, and
    which of the two bounds sets the least time."""
    p = peaks(device_kind)
    t_bytes, t_ops = bytes_ / p["hbm_bw"], ops / p["flops"]
    bound = "bytes" if t_bytes >= t_ops else "ops"
    return 100.0 * max(t_bytes, t_ops) / seconds, bound
