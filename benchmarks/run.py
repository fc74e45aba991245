"""One benchmark run: ``python3 benchmarks/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Driven by data. ``BENCHMARK.json`` names the cell's configuration and traffic
mix; ``configs/<config>.json`` and ``traffic/<traffic>.json`` are found by
those names; the traffic file names its driver (``drivers/<driver>.py``); and
with ``--trace 1`` each per-layer metric is read by
``layer_metrics/<metric>.py``. Adding a configuration, a traffic mix or a
per-layer metric means adding files and manifest entries, never editing one.

The last line of standard output is the result, one JSON object. A run that
finds no TPU (or too few chips) exits non-zero and prints no result, unless
``--rehearse``: tiny sizes on whatever backend is there, labelled
``"rehearsal": true``, for the tests. This module never imports JAX itself;
the driver does, once, in this same process, so that one process holds the chip.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``drivers/<name>.py`` or ``layer_metrics/<name>.py`` by file path
    (metric names hold dots, so they are not importable by name)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks/{kind}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reports(metric: dict, cell: dict, e2e_of_cell: set) -> bool:
    """Whether the manifest has this cell report this metric."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend; never a device number")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; have {sorted(cells)}")
    cell = cells[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    e2e = [m for m in manifest["end_to_end"] if reports(m, cell, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"] if reports(m, cell, e2e_names)]

    sys.path.insert(0, ROOT)  # the system under test
    sys.path.insert(0, HERE)  # the yardstick's own modules
    driver = load_module("drivers", traffic["driver"])
    result = driver.run(
        cell=cell, config=config, traffic=traffic, args=args,
        t_start=T_START, e2e=e2e, per_layer=per_layer,
        load_reader=lambda name: load_module("layer_metrics", name),
        out_dir=os.path.join(ROOT, "bench_out", cell["name"]),
    )
    for key, c in result["compared"].items():  # the driver puts it last
        print(f"compared {key} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
