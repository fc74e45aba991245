"""The readings that the limits of ``correct`` are set from, for the cells
of ``drivers/train_seq_cfg.py`` (the configuration names its reference), on
the chip. ``seq_readings.py`` is the mla/moe cell's and is tied to
``seq_reference``; this one reads the same numbers in the same order through
the configuration's own reference, and uses that script's helpers unchanged.

``python3 benchmarks/seq_cfg_readings.py --workload <cell> --seeds 1,2,3
[--control-seeds 2] [--program 0] [--faults a:8,b:1 | -] [--skip witness]``
prints one JSON line per seed: ``program`` (the timed path's call against the
plain reference) and ``program_leaves``; and for each of ``--control-seeds``,
with the reference put in the program's place, ``witness``, ``control`` (the
reference at the configuration's ``witness_precision`` / ``control_precision``)
with ``*_why``, and ``fault_<name>`` for each planted fault of the reference's
``FAULTS`` (``name:n`` trains the fault ``n`` steps only: its first-step numbers
are the whole call's, its ``update.*`` are read against the reference's state
after ``n`` steps). See ``seq_readings.py`` for what each number is and for the
order, which follows the host's memory.

Not part of a benchmark run. ``--rehearse`` runs it tiny, for the tests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--faults", default="")
    ap.add_argument("--skip", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    from run import load_json, load_module
    from seq_readings import Kept, Why, leaf_updates, say

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in manifest["workloads"]}[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    driver = load_module("drivers", load_json(
        HERE, "traffic", cell["traffic"] + ".json")["driver"])
    config = driver.sized(config, args.rehearse)
    from pio_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    job = driver.Job(config, driver.find_devices(cell["chips"], args.rehearse))
    R, m = job.ref_module, job.model
    per_step = R.TRACE_KEYS
    grouping = types.SimpleNamespace(  # what ``Why`` asks of a reference
        GROUPS=R.GROUPS, group_of=lambda path: R.group_of(path, m))
    limits = dict.fromkeys(  # the values are the look, limited or not
        [*config["limits"], *config.get("reported", {})], 0.0)
    control = {s for s in args.control_seeds.split(",") if s}
    skip = {s for s in args.skip.split(",") if s}
    faults = {}  # name -> steps it trains
    for spec in ([f for f in args.faults.split(",") if f != "-"]
                 if args.faults else R.FAULTS):  # "-": none
        name, _, n = spec.partition(":")
        faults[name] = min(int(n or job.steps), job.steps)
    lowered = {name: int(config[name + "_precision"]["mantissa_bits"])
               for name in ("witness", "control") if name not in skip}

    def numbers(got, ref):
        return {k: c["value"] for k, c in job.compare(got, ref, limits).items()}

    for seed in args.seeds.split(","):
        t = time.monotonic()
        job.set_seed(int(seed))
        out = {"workload": cell["name"], "seed": int(seed)}
        planted = seed in control
        if planted and lowered:  # compiled while the host's memory is empty
            job.reference(quantize=next(iter(lowered.values())), steps=1)
            say(compiled="the lowered program")
        got = None
        if args.program:
            _s, got = job.call()
            driver.release_device()
        short = {n for n in faults.values() if n < job.steps} if planted else set()
        kept = Kept(at=short, gradients=planted and bool(lowered))
        whole = args.program or lowered or not planted
        ref = job.reference(on_step=kept,
                            steps=None if whole else max(faults.values()))
        if args.program:
            out["program"] = numbers(got, ref)
            if got is not None:
                out["program_leaves"] = leaf_updates(
                    driver, driver.flat_params(got["params"]), ref)
            del got
            say(**{k: out[k] for k in out if k.startswith("program")})
        if planted:
            def in_its_place(result, n):
                """A reference result shaped as what a call hands back, held
                to the plain reference as far as that one trained."""
                trace = dict({k: result[k] for k in per_step}, dropped=[0.0])
                beside = ref if n == len(ref["l_main"]) else dict(
                    {k: ref[k][:n] for k in per_step},
                    init=ref["init"], final=kept.state[n])
                return numbers({"trace": trace, "params": result["final"]},
                               beside)

            for name, n in faults.items():
                key = "fault_" + name
                out[key] = in_its_place(job.reference(fault=name, steps=n), n)
                out[key]["steps"] = n
                say(**{key: out[key]})
            kept.state.clear()
            for name, bits in lowered.items():
                why = Why(grouping, driver, kept.g0)
                result = job.reference(quantize=bits, on_step=why)
                out[name] = in_its_place(result, job.steps)
                out[name + "_why"] = why.out
                if name == "witness":
                    out["witness_leaves"] = leaf_updates(
                        driver, result["final"], ref)
                say(**{k: out[k] for k in out if k.startswith(name)})
                del result
            kept.g0 = None
        out["seconds"] = time.monotonic() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
