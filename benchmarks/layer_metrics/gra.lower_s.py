"""Seconds JAX spent lowering jaxprs to MLIR modules inside the first call,
in the cell without experts. Read as the accepted
``setup.lower_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.lower_s").read
