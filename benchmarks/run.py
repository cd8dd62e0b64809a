"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (semantics of `derived` differ
per figure and are documented in each module).

  fig3  — normalized tokens/s vs Static across attention sparsity
  fig4  — normalized tokens/s vs Unlimited-HBM, low/high importance
          variation
  fig5  — HBM hit rates at 60% sparsity
  bound — SA upper bound headline (max speedup, W/R convergence,
          accepted-move attribution) + beyond-paper policies + TPU tiers

These are the paper's simulator figures, priced by the Eq.(1)-(5)
model on the CPU. The serving engine's speed on the chip is measured by
`bench/run.py`, whose per-cell `paged_attn_roofline`, `mfu` and `mbu`
are the chip's roofline numbers (`python -m repro.launch.dryrun`
compiles for forced CPU devices and measures no chip).
"""

from __future__ import annotations

import sys


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    from benchmarks import (fig3_sparsity, fig4_variation, fig5_hitrate,
                            upper_bound)
    suites = {
        "fig3": fig3_sparsity.run,
        "fig4": fig4_variation.run,
        "fig5": fig5_hitrate.run,
        "bound": upper_bound.run,
    }
    if which != "all":
        suites[which]()
        return
    for name, fn in suites.items():
        print(f"# --- {name} ---")
        fn()


if __name__ == '__main__':
    main()
