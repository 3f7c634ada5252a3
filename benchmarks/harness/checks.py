"""What ``correct`` is made of: numbers, each beside its limit."""

from __future__ import annotations

import math
from typing import List

import numpy as np


class Checks:
    """Collects ``name value limit`` triples; ``correct`` is true when
    every value is finite and within its limit.  ``print_all`` writes one
    line per number, as every run has to."""

    def __init__(self):
        self.rows: List[dict] = []

    def at_most(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        ok = math.isfinite(value) and value <= limit
        self.rows.append(
            {"name": name, "value": value, "limit": float(limit), "ok": ok}
        )

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print_all(self) -> None:
        for r in self.rows:
            print(
                f"check {r['name']}: {r['value']:.6g} "
                f"(limit {r['limit']:.6g}) {'ok' if r['ok'] else 'FAILED'}",
                flush=True,
            )


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The worst leaf's |norm(got) - norm(want)|, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  Both map leaf name -> norm."""
    norms = sorted(want.values())
    median = norms[len(norms) // 2]
    return max(
        abs(got[k] - want[k]) / max(want[k], median) for k in want
    )


def worst_leaf_difference(got: dict, want: dict) -> float:
    """The worst leaf's norm of (got - want), measured like
    ``worst_leaf_gap`` against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Both map leaf name -> array."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    median = sorted(norms.values())[len(norms) // 2]
    return max(
        float(np.linalg.norm(got[k] - want[k])) / max(norms[k], median)
        for k in want
    )


def float8(a):
    """The controls' rounding: through float8_e4m3fn and back."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
