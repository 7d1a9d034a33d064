"""The quantize kernel's share of its roofline: the least time its bytes or
operations take at the chip's peak (``yardstick.kernel_work``, counted
from the gradient's length, the bucket and the levels) over the summed
device time of its operations in the traced window."""
from bench import tracereduce, yardstick

KERNEL = "quantize"


def read(run):
    if run.trace is None or not run.cell.quantized:
        return None
    seconds, calls = tracereduce.kernel_time(run.trace, KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    wire = run.cell.wire
    bytes_, ops = yardstick.kernel_work(
        KERNEL, yardstick.num_coords(run.cell.config), wire["bucket"],
        2 ** wire["bits"])
    share, _ = yardstick.roofline_pct(bytes_ * calls, ops * calls, seconds,
                                      run.device_kind)
    return share
