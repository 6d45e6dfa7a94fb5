"""The control at the cell's own size, through the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip.py <workload> <quant> <seed,seed,...>`.

For each seed one short window (one call) with the reference's
lower-precision features in the program's place; prints each number compared
beside its limit and exits 0 only where every seed came out not correct.
Needs the cell's chips, like a run. Not part of a run of the benchmark."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import check, device, spec  # noqa: E402
from benchmarks.selfcheck.planted import with_control  # noqa: E402


def main(argv) -> int:
    workload, quant, seeds = argv[0], argv[1], [int(s) for s in argv[2].split(",")]
    cell = spec.load_cell(workload)
    device.fix_compile_cache()
    chips = device.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    failed_as_it_should = True
    for seed in seeds:
        t0 = time.perf_counter()
        res = driver.run(cell, with_control(cell, quant), chips, seed, 1.0,
                         False, t0)
        correct = check.verdict(res["compared"])
        failed_as_it_should &= not correct
        print(json.dumps({"seed": seed, "control": quant, "correct": correct,
                          "calls": res["calls"], "attempted": res["attempted"],
                          "failed": res["failed"],
                          "seconds": time.perf_counter() - t0,
                          "compared": check.as_dict(res["compared"])}), flush=True)
    return 0 if failed_as_it_should else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
