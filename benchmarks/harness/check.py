"""Numbers compared with the plain reference, each beside its limit."""

from __future__ import annotations

import math
import sys
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Compared:
    """One number of the comparison: correct while `value <= limit`."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def verdict(numbers: List[Compared]) -> bool:
    """True only where something was compared and every number holds."""
    return bool(numbers) and all(n.ok for n in numbers)


def as_dict(numbers: List[Compared]) -> Dict[str, Dict[str, float]]:
    return {n.name: {"value": n.value, "limit": n.limit} for n in numbers}


def print_last(numbers: List[Compared]) -> None:
    """The last lines on standard error: each number beside its limit."""
    for n in numbers:
        print(f"compared {n.name} value {n.value!r} limit {n.limit!r} "
              f"{'ok' if n.ok else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
