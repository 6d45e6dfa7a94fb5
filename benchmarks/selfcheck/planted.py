"""Builders with the timed path broken underneath: the harness's own run
(`driver.run`, the subject's `keep` / `check`, `check.verdict`) then has to
say not correct. Used by the tests here at a tiny size, and by
`control_on_chip.py` at the cell's own."""

from __future__ import annotations

import types


def with_fault(cell, fault):
    """A builder whose subject's `call` goes through `fault(output, n)`, where
    n counts the window's calls from 1 (the warm-up is not one of them)."""
    real = cell.module("builders", cell.config["builder"])

    def build(config, traffic, seed, chips):
        subject = real.build(config, traffic, seed, chips)
        call, state = subject.call, {"n": 0}

        def faulty():
            state["n"] += 1
            return fault(call(), state["n"])

        subject.call = faulty
        return subject

    return types.SimpleNamespace(build=build)


def with_control(cell, quant: str):
    """The reference in the program's place, one precision below the one the
    configuration states: every call of the window still drives the program,
    and returns the reference's lower-precision features of the same rows."""
    real = cell.module("builders", cell.config["builder"])

    def build(config, traffic, seed, chips):
        subject = real.build(config, traffic, seed, chips)
        call, state = subject.call, {}

        def control():
            out = call()
            if "features" not in state:     # the same rows in every call
                state["features"] = subject.reference.featurize(
                    config, seed, subject.images, quant=quant)
            assert state["features"].shape == out.shape
            return state["features"].copy()

        subject.call = control
        return subject

    return types.SimpleNamespace(build=build)
