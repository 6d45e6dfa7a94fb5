"""The scoring cell's control and planted faults at the cell's own size,
through the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip_kexaone.py <workload> <variant> <seed,seed,...> [seconds]`
with `<variant>` `fp8` (the control: the reference with every matrix
product's operands in float8) or one of the reference's `FAULTS`.

For each seed one window (one call, or `seconds` of calls) that drives the
program as a run does; in the comparison the variant's log-probabilities of
the SAMPLED rows stand in the program's place. They are computed where the
run computes its reference, after the program is freed: beside the program's
7.4 GB of weights no reference fits (`control_on_chip_bilstm.py` computes its
stand-in inside the call, which this cell's memory does not allow). Prints
each number compared beside its limit and exits 0 only where every seed came
out not correct. Needs the cell's chips, like a run. Not part of a run."""

from __future__ import annotations

import json
import os
import sys
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import check, device, spec  # noqa: E402


def with_variant(cell, variant: str):
    """A builder whose window drives the program and whose comparison reads
    the reference's log-probabilities of the sampled rows computed as
    `variant` says, in the program's place."""
    real = cell.module("builders", cell.config["builder"])
    how = {"quant": variant} if variant == "fp8" else {"fault": variant}

    def build(config, traffic, seed, chips):
        subject = real.build(config, traffic, seed, chips)
        compare = subject.compare

        def stand_in(idx, got):
            need, at = np.unique(idx, return_inverse=True)
            alt = subject.reference.score(config, seed, subject.ids[need], **how)
            assert alt["logprob"][at].shape == got.shape
            return compare(idx, alt["logprob"][at])

        subject.compare = stand_in
        return subject

    return types.SimpleNamespace(build=build)


def main(argv) -> int:
    workload, variant, seeds = argv[0], argv[1], [int(s) for s in argv[2].split(",")]
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    cell = spec.load_cell(workload)
    device.fix_compile_cache()
    chips = device.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    failed_as_it_should = True
    for seed in seeds:
        t0 = time.perf_counter()
        res = driver.run(cell, with_variant(cell, variant), chips, seed, seconds,
                         False, t0)
        correct = check.verdict(res["compared"])
        failed_as_it_should &= not correct
        check.print_last(res["compared"])
        print(json.dumps({"seed": seed, "variant": variant, "correct": correct,
                          "calls": res["calls"], "attempted": res["attempted"],
                          "failed": res["failed"],
                          "seconds": time.perf_counter() - t0,
                          "compared": check.as_dict(res["compared"])}), flush=True)
    return 0 if failed_as_it_should else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
