"""Seconds JAX spent tracing jitted functions to jaxprs inside the first
call, in the cell without experts (twenty unrolled mixers). Read as the accepted
``setup.trace_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.trace_s").read
