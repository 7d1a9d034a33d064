"""On-chip benchmark of quantized data-parallel training (see run.py)."""
