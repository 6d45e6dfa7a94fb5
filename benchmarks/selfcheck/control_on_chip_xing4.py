"""The latent cell's control and planted faults at the cell's own size, through
the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip_xing4.py <workload>
<variant,variant,...|all> <seed,seed,...> [seconds]` with each `<variant>` `fp8` (the control: the reference with every matrix
product's operands in float8) or one of the reference's `FAULTS`; `all` is the
control and every fault.

As `control_on_chip_kexaone.py` (whose `with_variant` does one variant a
window), with the one difference that a window's sampled rows serve every
variant named: for each seed ONE window drives the program as a run does (one
call, or `seconds` of calls), the true reference of the sampled rows is
computed once, and each variant's log-probabilities of those rows stand in
the program's place in the builder's own `compare`, one variant after the
other. Beside 8.35 GB of weights no reference fits, so all of that happens
where the run computes its reference, after the program is freed. Prints each
variant's numbers beside their limits and exits 0 only where every variant of
every seed came out not correct. Needs the cell's chips, like a run. Not part
of a run."""

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


def with_variants(cell, variants, verdicts):
    """A builder whose window drives the program and whose comparison reads,
    for each variant in turn, the reference's log-probabilities of the sampled
    rows computed as the variant says, in the program's place. `verdicts`
    gains `{variant: [Compared]}`; the run's own numbers are the last one's."""
    real = cell.module("builders", cell.config["builder"])

    def build(config, traffic, seed, chips):
        subject = real.build(config, traffic, seed, chips)
        compare, reference = subject.compare, subject.reference
        truth = {}

        def score(config, seed, ids, **how):
            # `compare` asks for the true reference of the same rows once a
            # variant: it is computed once a window
            if how:
                return reference.score(config, seed, ids, **how)
            if ids.tobytes() not in truth:
                truth[ids.tobytes()] = reference.score(config, seed, ids)
            return truth[ids.tobytes()]

        def stand_in(idx, got):
            need, at = np.unique(idx, return_inverse=True)
            for variant in variants:
                how = {"quant": variant} if variant == "fp8" else {"fault": variant}
                alt = score(config, seed, subject.ids[need], **how)["logprob"][at]
                assert alt.shape == got.shape
                verdicts[variant] = compare(idx, alt)
            return verdicts[variants[-1]]

        subject.reference = types.SimpleNamespace(score=score, row_gaps=reference.row_gaps)
        subject.compare = stand_in
        return subject

    return types.SimpleNamespace(build=build)


def main(argv) -> int:
    workload, seeds = argv[0], [int(s) for s in argv[2].split(",")]
    seconds = float(argv[3]) if len(argv) > 3 else 1.0
    cell = spec.load_cell(workload)
    reference = cell.module("references", cell.config["reference"])
    variants = ["fp8", *reference.FAULTS] if argv[1] == "all" else argv[1].split(",")
    device.fix_compile_cache()
    chips = device.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    failed_as_it_should = True
    for seed in seeds:
        t0 = time.perf_counter()
        verdicts = {}
        res = driver.run(cell, with_variants(cell, variants, verdicts), chips, seed,
                         seconds, False, t0)
        for variant, numbers in verdicts.items():
            correct = check.verdict(numbers)
            failed_as_it_should &= not correct
            print(json.dumps({"seed": seed, "variant": variant, "correct": correct,
                              "calls": res["calls"], "attempted": res["attempted"],
                              "failed": res["failed"],
                              "compared": check.as_dict(numbers)}), flush=True)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if failed_as_it_should else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
