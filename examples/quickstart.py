"""Quickstart: train a small model with adaptively quantized (ALQ, 3-bit)
data-parallel SGD on a learnable synthetic task, and watch (a) the loss
fall and (b) the quantization grid adapt to the gradient distribution.

  PYTHONPATH=src python examples/quickstart.py

``--no-use-pallas`` runs the jnp reference of the kernels: on a CPU the
Pallas kernels would run in the (much slower) interpreter.
"""
import subprocess
import sys

subprocess.run(
    [sys.executable, "-m", "repro.launch.train",
     "--arch", "paper-proxy", "--scheme", "alq", "--bits", "3",
     "--steps", "40", "--lr", "2e-3", "--no-use-pallas"],
    check=True)
