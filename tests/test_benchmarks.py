"""Tier-1 collects ``tests/`` only; the benchmark keeps its own tests beside
the yardstick, in ``benchmarks/tests``. This module imports them and hands
their test functions to pytest under ``test_<file>__<name>``, so every later
PR runs them (ROADMAP A0).

Their ``from conftest import BENCH, ROOT`` means ``benchmarks/tests/
conftest.py``, which puts ``benchmarks/`` on ``sys.path``; ``tests/
conftest.py`` already owns the module name here, so it is lent out for the
duration of the imports.
"""

import glob
import importlib.util
import os
import sys

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmarks", "tests")


def _load(path: str):
    name = "benchmarks_tests_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_ours = sys.modules.get("conftest")
sys.modules["conftest"] = _load(os.path.join(_DIR, "conftest.py"))
try:
    for _path in sorted(glob.glob(os.path.join(_DIR, "test_*.py"))):
        _stem = os.path.basename(_path)[:-3]
        for _name, _obj in vars(_load(_path)).items():
            if _name.startswith("test_") and callable(_obj):
                globals()[f"{_stem}__{_name[len('test_'):]}"] = _obj
finally:
    if _ours is None:
        del sys.modules["conftest"]
    else:
        sys.modules["conftest"] = _ours
