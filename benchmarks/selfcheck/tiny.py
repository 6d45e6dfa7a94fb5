"""Tiny copies of the cells for the self-checks: the same files and code
paths, sizes that the CPU holds. Never a source of a device number."""

from __future__ import annotations

import copy
import dataclasses

from benchmarks.harness import spec

_TINY_CONFIG = {
    "resnet50-featurize": {"width": 8,
                           "image_size": 32, "num_classes": 10,
                           "assumed.batch_size": 8},
}
_TINY_TRAFFIC = {
    "dataframe-batch": {"batches_per_call": 2, "source_px": 40,
                        "check_rows_per_call": 4, "check_rows_last_call": 8},
}


def _override(d: dict, changes: dict) -> dict:
    d = copy.deepcopy(d)
    for key, value in changes.items():
        node = d
        *parents, leaf = key.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = value
    return d


def tiny_cell(workload: str) -> spec.Cell:
    cell = spec.load_cell(workload)
    return dataclasses.replace(
        cell,
        config=_override(cell.config, _TINY_CONFIG[cell.config_name]),
        traffic=_override(cell.traffic, _TINY_TRAFFIC[cell.traffic_name]))
