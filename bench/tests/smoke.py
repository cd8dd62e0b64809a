"""Small sizes at which the tests run a cell on the CPU."""

from __future__ import annotations

import copy
import time

SIZES = dict(num_layers=2, d_model=64, num_heads=4, kv_heads=2, head_dim=16,
             d_ff=128, vocab=256)
#: stands in for the chip's peaks (the CPU has none in the table)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELL = "internlm2-1.8b.decode-spill"


def config(base, **sizes):
    out = copy.deepcopy(base)
    out["sizes"].update(SIZES, **sizes)
    return out


def closed_traffic(base, lanes=4, max_context=256, n=10, warm_s=0.0,
                   **engine):
    out = copy.deepcopy(base)
    out["traffic"].update(
        loop="closed", n_requests=n, block=0, staggered=False, warm_s=warm_s,
        prompt={"dist": "uniform", "lo": 40, "hi": 120},
        output={"dist": "uniform", "lo": 8, "hi": 24})
    out["engine"].update(lanes=lanes, max_context=max_context,
                         telemetry_stride=8, **engine)
    return out


def run(name=CELL, seed=3, seconds=3.0, trace=False, root=None, **kw):
    from bench import harness
    cell, conf, traffic, _ = harness.load_cell(name, root or harness.ROOT)
    kw.setdefault("config", config(conf))
    kw.setdefault("traffic", closed_traffic(traffic))
    return harness.run_cell(name, seed, seconds, trace,
                            t_process=time.time(),
                            root=root or harness.ROOT, log=lambda m: None,
                            peaks=PEAKS, **kw)
