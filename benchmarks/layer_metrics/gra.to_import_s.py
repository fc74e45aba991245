"""Seconds from the OS's start of the process to the first import of
``pio_tpu``, in the cell without experts. Read as the accepted
``setup.to_import_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.to_import_s").read
