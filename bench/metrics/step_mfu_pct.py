"""Model FLOP utilization of the whole step: the forward and backward
FLOPs per token of the configuration (``yardstick.model_flops_per_token``,
no recomputation) times the tokens per second of the window, over the
chips' peak (``yardstick.PEAKS``)."""
from bench import yardstick


def read(run):
    if run.tokens_per_s is None:
        return None
    flops = yardstick.model_flops_per_token(run.cell.config,
                                            run.cell.job["seq_len"])
    peak = yardstick.peaks(run.device_kind)["flops"] * run.chips
    return 100.0 * flops * run.tokens_per_s / peak
