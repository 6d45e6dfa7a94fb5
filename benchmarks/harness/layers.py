"""Run the cell's per-layer readers: one file each, found by the metric's name."""

from __future__ import annotations

import sys
from typing import Any, Dict

from .spec import Cell


def read_layers(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """{metric: {"value", "unit"}} of every reader that found something to
    read. One that finds nothing (returns None or raises LookupError) is left
    out of the line and named on standard error; it never reads as 0."""
    out: Dict[str, Dict[str, Any]] = {}
    for metric in cell.per_layer:
        name = metric["name"]
        reader = cell.module("layer_metrics", name)
        try:
            value = reader.read(ctx)
        except LookupError as e:
            print(f"layer metric {name}: nothing to read ({e})", file=sys.stderr)
            continue
        if value is None:
            print(f"layer metric {name}: nothing to read", file=sys.stderr)
            continue
        out[name] = {"value": float(value), "unit": metric["unit"]}
    return out
