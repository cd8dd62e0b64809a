"""Run one cell of the benchmark on the chip and print one JSON line.

    python3 bench/run.py --workload internlm2-1.8b.decode-spill \\
        --seed 7 --seconds 30 --trace 0

Set-up (weights made on the device from the seed, the serve chunk
compiled or loaded from the compile cache, one warm-up serve) counts
as `setup_s`. The window serves the cell's seeded traffic through
`ServingEngine.serve()` for `--seconds`; then the served tokens are
checked against the plain reference. With `--trace 1` the last seconds
of the window are recorded by the profiler, and the line carries the
cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is the result; the numbers compared
for `correct` are also the last lines of standard error. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def compile_cache(jax) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where `JAX_COMPILATION_CACHE_DIR` says), every program kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)[0]

    import jax
    compile_cache(jax)
    devices = jax.devices()
    log(f"JAX found its devices {time.time() - T_PROCESS:.3f} s after "
        "the process started")
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        log(f"bench: needs {cell['chips']} TPU chip(s), JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              log=log)
    log(f"correct {result['correct']}")
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
