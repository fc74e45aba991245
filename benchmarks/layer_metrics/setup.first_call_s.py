"""Seconds of the first train call of the process, the harness's warm call,
enter to exit. With ``setup.to_import_s``, ``setup.to_context_s`` and
``setup.to_first_call_s`` it sums to the first call's exit since the process
began: the harness's ``setup_s`` plus the interpreter's own start."""

from process_record import between


def read(reading):
    return between(reading, "first_call_enter", "first_call_exit")
