"""The token cell's control and planted faults at the cell's own size, through
the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip_bilstm.py <workload> <variant> <seed,seed,...> [seconds]`
with `<variant>` one of `fp8` (the control: the reference with every matrix
product's operands in float8), `bwd_forward`, `gates` (planted faults).

For each seed one window (one call, or `seconds` of calls: 51 compares as many
rows as a run does) with the reference's logits of the same rows in the
program's place; prints each number compared beside its
limit and exits 0 only where every seed came out not correct. Needs the
cell's chips, like a run. Not part of a run of the benchmark.
(`control_on_chip.py` is the image cell's: its `planted.with_control` asks
the reference for `featurize(images)`.)"""

from __future__ import annotations

import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import check, device, spec  # noqa: E402


def with_reference(cell, variant: str):
    """A builder whose calls still drive the program, and return the
    reference's logits of the same rows computed as `variant` says: one
    precision below the configuration's (`fp8`), or with a fault planted."""
    real = cell.module("builders", cell.config["builder"])
    how = {"quant": variant} if variant == "fp8" else {"fault": variant}

    def build(config, traffic, seed, chips):
        subject = real.build(config, traffic, seed, chips)
        call, state = subject.call, {}

        def stand_in():
            out = call()
            if "logits" not in state:       # the same rows in every call
                state["logits"] = subject.reference.tag(
                    config, seed, subject.ids, **how)
            assert len(state["logits"]) == len(out)
            return subject.column_of(state["logits"])

        subject.call = stand_in
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
        res = driver.run(cell, with_reference(cell, variant), chips, seed, seconds,
                         False, t0)
        correct = check.verdict(res["compared"])
        failed_as_it_should &= not correct
        print(json.dumps({"seed": seed, "variant": variant, "correct": correct,
                          "calls": res["calls"], "attempted": res["attempted"],
                          "failed": res["failed"],
                          "seconds": time.perf_counter() - t0,
                          "compared": check.as_dict(res["compared"])}), flush=True)
    return 0 if failed_as_it_should else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
