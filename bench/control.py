"""Readings that the limit of `correct` is set from, on the chip.

For each seed, one window of the cell as a run serves it, then the
check's sample read twice: the program's widest logit gap below the
float32 reference's best (the number a run compares), and the
control's, the reference computed with float8 matmul operands in the
program's place. One process sets the cell up once and changes only
the weights and traffic between seeds.

    python3 bench/control.py --workload internlm2-1.8b.decode-spill \\
        --seconds 30 --seeds 11 12 13

The benchmark's own runs never run this. The last line of standard
output is a JSON object with the readings per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench import harness  # noqa: E402
from bench.run import compile_cache  # noqa: E402


def readings(session: harness.Session, seeds, seconds: float,
             lowp: str = "fp8", log=print) -> list:
    out = []
    for i, seed in enumerate(seeds):
        if i:
            session.reseed(seed)
        served = session.window(session.stream(seed, seconds), seed,
                                seconds)
        got = session.check(served, seed, lowp=lowp)
        got["seed"] = seed
        got["output_tokens"] = sum(len(r["output"]) for r in served.requests)
        log(json.dumps(got))
        out.append(got)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("control: runs only on a TPU", file=sys.stderr)
        return 2
    t = time.time()
    session = harness.Session(args.workload, args.seeds[0])
    print(f"set-up {time.time() - t:.1f} s", flush=True)
    got = readings(session, args.seeds, args.seconds)
    print(json.dumps({"workload": args.workload, "readings": got}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
