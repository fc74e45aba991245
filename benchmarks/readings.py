"""The readings that the limits of ``correct`` are set from, on the chip.

``python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 [--control-seeds
2] [--program 0]`` prints one JSON line per seed. In one process (set-up is long), for each seed:

- ``program``: the timed path's call against the plain reference (the lower
  readings);
- and for each of ``--control-seeds``, with the reference put in the
  program's place: ``control`` (the reference in the precision below the
  configuration's, ``control_precision``), ``fault_unchanged`` (the last
  iteration returns its state unchanged: one iteration fewer),
  ``fault_half`` (every second edge left out), ``fault_altered`` (two
  neighbouring rows of each table swapped where it is produced).

Not part of a benchmark run. ``--rehearse`` runs it tiny, for the tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def numbers(job, got: dict, ref: dict) -> dict:
    """The compared numbers, and beside them ``rowmax_all`` (every row, the
    ill-determined ones too) and the worst row's degree: the look."""
    import compare
    import numpy as np

    out = {}
    for name, r in ref.items():
        g, deg = got.get(name), job.degrees[name]
        for k, v in compare.table_numbers(g, r, deg, job.min_degree).items():
            out[f"{name}.{k}"] = v
        if g is not None and np.isfinite(g).all():
            err = compare.row_errors(g, np.asarray(r, np.float64))
            out[f"{name}.rowmax_all"] = float(err.max())
            out[f"{name}.rowmax_all_degree"] = int(deg[int(err.argmax())])
            out[f"{name}.row_p99"] = float(np.quantile(err, 0.99))
        elif g is not None:
            out[f"{name}.non_finite_rows"] = int((~np.isfinite(g).all(axis=1)).sum())
    return out


def swap_rows(job, tables: dict) -> dict:
    """Two well-determined rows of each table change places."""
    import numpy as np

    out = {}
    for name, t in tables.items():
        t = t.copy()
        a, b = np.nonzero(job.degrees[name] >= job.min_degree)[0][[0, -1]]
        t[[a, b]] = t[[b, a]]
        out[name] = t
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import numpy as np
    from run import load_json, load_module

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    driver = load_module("drivers", load_json(
        HERE, "traffic", cell["traffic"] + ".json")["driver"])
    config = driver.sized(config, args.rehearse)
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    job = driver.Job(config, driver.find_devices(cell["chips"], args.rehearse))
    control = {s for s in args.control_seeds.split(",") if s}
    for seed in args.seeds.split(","):
        t = time.monotonic()
        job.set_seed(int(seed))
        ref = job.reference()
        out = {"workload": cell["name"], "seed": int(seed)}
        if args.program:
            _s, tables = job.call()
            out["program"] = numbers(job, tables or {}, ref)
        if seed in control:
            low = config["control_precision"]
            low = (int(low["exponent_bits"]), int(low["mantissa_bits"]))
            out["control"] = numbers(job, job.reference(quantize=low), ref)
            out["fault_unchanged"] = numbers(
                job, job.reference(iterations=job.iterations - 1), ref)
            keep = np.arange(len(job.user_idx)) % 2 == 0
            out["fault_half"] = numbers(job, job.reference(keep=keep), ref)
            out["fault_altered"] = numbers(job, swap_rows(job, ref), ref)
        out["seconds"] = time.monotonic() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
