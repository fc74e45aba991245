"""The readings that the limits of ``correct`` are set from, for the sequence
template's cells, on the chip (``readings.py`` is the ALS cells' and knows
factor tables; this one knows ``train_seq.compare_call``'s numbers).

``python3 benchmarks/seq_readings.py --workload <cell> --seeds 1,2,3
[--control-seeds 2] [--program 0] [--faults a:8,b:1 | -] [--skip witness]``
prints one JSON line per seed. In one process (set-up is long), per seed:

- ``program``: the timed path's call against the plain reference (the lower
  readings), and ``program_leaves``: ``update`` per parameter;
- and for each of ``--control-seeds``, with the reference put in the
  program's place: ``witness`` (the reference with its matmul operands in
  the configuration's own precision, ``witness_precision``: what rounding
  alone does to the plain equations, read beside the program), ``control``
  (the precision below, ``control_precision``) and ``fault_<name>`` for each
  planted fault of ``seq_reference.FAULTS``. ``--faults name:n`` trains that
  fault for the first ``n`` steps only: its first-step numbers (``grad.*``,
  ``pairs``) are the whole call's, ``loss.*`` and ``later.*`` are largest
  differences over those steps, lower bounds of the whole call's, and its
  ``update.*`` are read against the reference's state after ``n`` steps.
  ``--skip witness,control`` leaves those out (the two share one compiled
  program; the faults share the plain reference's).
- ``witness_why`` / ``control_why``, per parameter group, from the first
  step's gradients ``g`` beside the plain reference's ``g_ref``: ``grad_rel``
  (``||g - g_ref|| / ||g_ref||``), ``sign_flips`` (the share of entries whose
  sign differs), ``update_1`` (``update`` after Adam's first step, which from
  zero moments is ``-lr * g / (|g| + eps)`` entry by entry) and ``predicted``
  (``2 * sqrt(sign_flips)``: that step taken as a pure sign step).

The order within a seed follows the host's memory (40 GiB on the chip's
machine; a result is 2.8 GB, a training reference 11 GB, the compiler takes
its own): the lowered program compiles first, on one step; the faults run
before the witness and the control; what a stage kept goes when it is done.

Not part of a benchmark run. ``--rehearse`` runs it tiny, for the tests.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

PER_STEP = ("l_main", "l_mtp", "pairs", "grad_norm")


def say(**what) -> None:
    """A stage's numbers on stderr as they come, with the process's largest
    resident size so far: a run that is cut keeps what it had read."""
    import resource

    what["maxrss_gb"] = resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9
    print(json.dumps(what), file=sys.stderr, flush=True)


ADAM_EPS = 1e-8


class Kept:
    """What a reference run is asked to keep on its way (``on_step``): its
    first gradients and its state after each of ``at`` steps."""

    def __init__(self, at=(), gradients=False):
        self.at, self.gradients = set(at), gradients
        self.g0, self.state = None, {}

    def __call__(self, i, params, grads):
        if i == 0 and self.gradients:
            self.g0 = {k: np.array(v) for k, v in grads.items()}
        if i in self.at:
            self.state[i] = {k: v.copy() for k, v in params.items()}


def first_step(g):
    """Adam's first step over the learning rate: ``g / (|g| + eps)``."""
    return g / (np.abs(g) + np.float32(ADAM_EPS))


class Why:
    """Reads a lowered run's first gradients beside the plain ones: how far
    they lie apart, entry by entry, and what Adam's first step makes of
    that."""

    def __init__(self, seq_reference, driver, g0: dict):
        self.R, self.ssd, self.g0 = (seq_reference,
                                     driver._sum_squares_diff, g0)
        self.out = {}

    def __call__(self, i, params, grads):
        if i:
            return
        sums = {k: dict.fromkeys(self.R.GROUPS, 0.0) for k in
                ("diff", "norm", "flips", "size", "step_diff", "step_norm")}
        for path, g in grads.items():
            g, g0 = np.asarray(g), self.g0[path]
            group = self.R.group_of(path)
            zero = np.zeros((), np.float32)
            sums["diff"][group] += self.ssd(g, g0)
            sums["norm"][group] += self.ssd(g0, np.broadcast_to(zero, g0.shape))
            sums["flips"][group] += float(np.count_nonzero((g > 0) != (g0 > 0)))
            sums["size"][group] += float(np.count_nonzero(g0))
            step0 = first_step(g0)
            sums["step_diff"][group] += self.ssd(first_step(g), step0)
            sums["step_norm"][group] += self.ssd(
                step0, np.broadcast_to(zero, g0.shape))
        for group in self.R.GROUPS:
            if sums["norm"][group] > 0:
                share = sums["flips"][group] / max(sums["size"][group], 1.0)
                self.out[group] = {
                    "grad_rel": math.sqrt(
                        sums["diff"][group] / sums["norm"][group]),
                    "sign_flips": share,
                    "update_1": math.sqrt(sums["step_diff"][group]
                                          / sums["step_norm"][group]),
                    "predicted": 2.0 * math.sqrt(share)}


def leaf_updates(driver, params: dict, ref: dict) -> dict:
    """``update`` of every parameter alone: ``||final - reference final|| /
    ||reference final - init||``."""
    ssd, out = driver._sum_squares_diff, {}
    for path, final in ref["final"].items():
        moved = ssd(final, ref["init"][path])
        if moved > 0:
            out[path] = math.sqrt(ssd(params[path], final) / moved)
    return out


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
    import seq_reference
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
    limits = dict.fromkeys(  # the values are the look, limited or not
        [*config["limits"], *config.get("reported", {})], 0.0)
    control = {s for s in args.control_seeds.split(",") if s}
    skip = {s for s in args.skip.split(",") if s}
    faults = {}  # name -> steps it trains
    for spec in ([f for f in args.faults.split(",") if f != "-"]
                 if args.faults else seq_reference.FAULTS):  # "-": none
        name, _, n = spec.partition(":")
        faults[name] = min(int(n or job.steps), job.steps)
    lowered = {name: int(config[name + "_precision"]["mantissa_bits"])
               for name in ("witness", "control") if name not in skip}

    def numbers(got, ref):
        return {k: c["value"] for k, c in
                driver.compare_call(got, ref, limits).items()}

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
        # the program's call is held to the whole reference; the faults alone
        # need no more of it than they train themselves
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
                trace = dict({k: result[k] for k in PER_STEP}, dropped=[0.0])
                beside = ref if n == len(ref["l_main"]) else dict(
                    {k: ref[k][:n] for k in PER_STEP},
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
                why = Why(seq_reference, driver, kept.g0)
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
