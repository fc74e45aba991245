"""Seconds inside the first call in XLA's compiler or loading executables
from the persistent cache, in the cell without experts. Read as the accepted
``setup.load_or_compile_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.load_or_compile_s").read
