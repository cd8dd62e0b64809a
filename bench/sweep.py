"""Find an open-loop cell's knee once: serve its traffic at several
arrival rates, each for one window, and print what was offered against
what was served.

    python3 bench/sweep.py --workload internlm2-1.8b.chat-poisson \\
        --seconds 30 --rates 1.0 1.5 2.0 --seed 5

The knee is the highest rate whose output keeps up with the tokens
offered and whose queue does not grow through the window. The cell's
traffic file then fixes its rate; the benchmark's runs never sweep.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench import harness  # noqa: E402
from bench.run import compile_cache  # noqa: E402

READS = ("output_tok_s", "ttft_p95_s", "tpot_p95_ms", "queue_wait_p95_s")


def at_rate(session: harness.Session, rate: float, seed: int,
            seconds: float) -> dict:
    session.traffic = copy.deepcopy(session.traffic)
    session.traffic["traffic"]["rate_rps"] = rate
    stream = session.stream(seed, seconds)
    served = session.window(stream, seed, seconds)
    ctx = session.context(served, seconds)
    out = {"rate_rps": rate, "requests": stream.n,
           "offered_tok_s": float(stream.max_new.sum())
           / (session.warm_s + seconds),
           "never_admitted": sum(r["admitted"] is None
                                 for r in served.requests),
           "finished": sum(r["status"] == "ok" for r in served.requests)}
    for name in READS:
        out[name] = harness.read_metric(name, ctx)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("sweep: runs only on a TPU", file=sys.stderr)
        return 2
    session = harness.Session(args.workload, args.seed)
    rows = []
    for rate in args.rates:
        rows.append(at_rate(session, rate, args.seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
