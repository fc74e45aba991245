"""Seconds from the first import of ``pio_tpu`` to the first
``ComputeContext`` (importing JAX, reaching the chip), in the cell without
experts. Read as the accepted
``setup.to_context_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.to_context_s").read
