"""One run of one cell: `python3 benchmarks/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

A new process each time. Finds the cell in BENCHMARK.json, its configuration,
traffic mix, driver, builder, reference and per-layer readers by name; exits
non-zero and prints no result without the cell's chips. The last line of
standard output is the result. This file knows no cell, configuration or
metric.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()          # set-up is counted from here

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import check, device, spec  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    device.fix_compile_cache()
    chips = device.require_chips(cell.chips)
    driver = cell.module("drivers", cell.traffic["driver"])
    builder = cell.module("builders", cell.config["builder"])
    result = driver.run(cell, builder, chips, args.seed, args.seconds,
                        bool(args.trace), _T_START)
    numbers = result.pop("compared")
    line = {"correct": check.verdict(numbers), **result,
            "compared": check.as_dict(numbers)}
    check.print_last(numbers)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
