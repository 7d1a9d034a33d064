"""Bits the quantized wire ships per gradient coordinate, codes and
norms: the program's own count (``comm_bits_per_coord``) of the
window's last step."""


def read(run):
    if run.step_metrics is None or not run.cell.quantized:
        return None
    return run.step_metrics["comm_bits_per_coord"]
