"""The first train call less the second (in a traced run the profiled call):
what the warm call costs beyond a call."""

from process_record import first_call_excess

read = first_call_excess
