"""What the first call costs beyond a call that no event of JAX's compile
path holds, in the cell without experts. Read as the accepted
``setup.unexplained_s``."""

from run import load_module

read = load_module("layer_metrics", "setup.unexplained_s").read
