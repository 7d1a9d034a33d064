"""Normalized variance of the quantized wire, sum E[(Q(g) - g)^2] /
||g||^2, on the levels the run adapted (``Run.quant_nvar``)."""


def read(run):
    return run.quant_nvar()
