"""Host seconds of bringing the trained parameters back after the last step
(the stats call's ``seq.readback`` phase) in the cell of single mixers: 2.7
GB a call, the idle gap after the last device operation and the part of a
call that varies from run to run. Read as the mla/moe cell's
``seq.readback_s``."""

from run import load_module

read = load_module("layer_metrics", "seq.readback_s").read
