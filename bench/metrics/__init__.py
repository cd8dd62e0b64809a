"""One module per metric, found by the metric's name in BENCHMARK.json.

Each defines `read(ctx) -> float | None` over a `harness.RunContext`;
None means the run had nothing to read for it.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def p95(values: Sequence[float]):
    """The 95th percentile (linear between ranks), None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))


def due_in_window(ctx) -> List[Dict]:
    """The requests due from the window's opening to its end (not those
    of the warm-in before it)."""
    return [r for r in ctx.requests if ctx.t0 <= r["due"] <= ctx.t1]
