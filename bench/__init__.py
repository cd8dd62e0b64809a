"""The chip benchmark: one cell of `BENCHMARK.json` per run.

`python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` serves one seeded traffic mix through
`ServingEngine.serve()` on the chip and prints one JSON line. The
pieces are found by name: `configs/<config>.json`,
`traffic/<mix>.json`, `metrics/<metric>.py`, `peaks.json`.
"""
