"""The sparse cell's control and planted faults at the cell's own size,
through the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip_axk2.py axk2.score
<variant,variant,...|all> <seed,seed,...> [seconds]` with each `<variant>`
`fp8` (the control: the reference with every matrix product's operands in
float8) or one of the reference's `FAULTS` (dense attention over every causal
key; the top 1,024 keys; the indexer without its ReLU; the index weights
uniform; no output gate; no gated norm; routing without groups); `all` is the
control and every fault.

It is `control_on_chip_xing4.py`'s arithmetic (that file knows no cell: ONE
window drives the program as a run does, the true reference of the sampled
rows is computed once, and each variant's log-probabilities of those rows
stand in the program's place in the builder's own `compare`), under this
cell's name so that the cell's files are found together. Exits 0 only where
every variant of every seed came out not correct. Needs the cell's chip, like
a run. Not part of a run."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.selfcheck.control_on_chip_xing4 import main, with_variants  # noqa: E402,F401

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
