"""The multi-token-prediction cell's control and planted faults at the cell's
own size, through the harness's own run:
`python3 benchmarks/selfcheck/control_on_chip_glm47.py glm47flash.score
<variant,variant,...|all> <seed,seed,...> [seconds]` with each `<variant>`
`fp8` (the control: the reference with every matrix product's operands in
float8) or one of the reference's `FAULTS` (the MTP embeds id_t; it reads the
final-normed state; RMSNorm_h left out; its targets one ahead; the routed
scale 1.0; no shared expert; the content heads cut to 128); `all` is the
control and every fault.

It is `control_on_chip_xing4.py`'s arithmetic (ONE window drives the program
as a run does, the true reference of the sampled rows is computed once, and
each variant's answers for those rows stand in the program's place in the
builder's own `compare`), with both outputs standing in: a variant's
`logprob` and `mtp_logprob` as the `[2, cap]` rows the builder compares.
Exits 0 only where every variant of every seed came out not correct. Needs
the cell's chip, like a run. Not part of a run."""

from __future__ import annotations

import os
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def with_variants(cell, variants, verdicts):
    """A builder whose window drives the program and whose comparison reads,
    for each variant in turn, the reference's two log-probabilities of the
    sampled rows computed as the variant says, in the program's place.
    `verdicts` gains `{variant: [Compared]}`; the run's own numbers are the
    last one's."""
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
                alt = score(config, seed, subject.ids[need], **how)
                alt = np.stack([alt["logprob"][at], alt["mtp_logprob"][at]], axis=1)
                assert alt.shape == got.shape
                verdicts[variant] = compare(idx, alt)
            return verdicts[variants[-1]]

        subject.reference = types.SimpleNamespace(score=score, row_gaps=reference.row_gaps)
        subject.compare = stand_in
        return subject

    return types.SimpleNamespace(build=build)


if __name__ == "__main__":
    # xing4's `main` (one window a seed, every variant, the verdicts printed)
    # with this cell's `with_variants` in place of its own
    from benchmarks.selfcheck import control_on_chip_xing4 as _xing4

    _xing4.with_variants = with_variants
    sys.exit(_xing4.main(sys.argv[1:]))
