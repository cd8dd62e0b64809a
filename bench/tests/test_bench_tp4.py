"""The four-chip cell's path on four host devices, in a process of its
own (`tp4.py`), so that it runs on a host with one device too.

On a data=1, model=4 mesh (one KV head a device), prefill then decode
through the paged cache agree with the plain reference: within 2e-4 in
float32, as on one device (`test_bench_reference.py`), and within a
tenth of the logits' spread in bf16, whose rounding the model axis's
partial sums do not widen past that. The harness serves
`granite-8b.decode-tp4` at a small size on the mesh and finds it
correct, and each serve call makes its cache already sharded: the
whole cache of the cell (10.3 GB) does not fit one chip beside its
share of the weights.
"""

import json
import os
import subprocess
import sys

import pytest

from bench import harness


def _run(what: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(harness.ROOT, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-m", "bench.tests.tp4", what],
                          capture_output=True, text=True, env=env,
                          cwd=harness.ROOT, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_meshed_prefill_then_decode_matches_reference(dtype):
    got = _run(dtype)
    assert got["devices"] == 4
    # pools sharded by KV heads over the model axis
    assert got["pool_spec"][4] == "model"
    assert got["hbm_tokens"] < 50                  # the prompt spilled
    assert got["shape"] == [21, 256]
    if dtype == "float32":
        assert got["max_abs"] < 2e-4, got
    else:
        assert got["max_abs"] < 0.1 * got["spread"], got


def test_four_chip_cell_runs_correct_on_host_devices():
    r = _run("cell")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 10
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}
    # every serve call made its cache sharded, each device its part
    # (traced once: the window's call reuses the warm-up's program)
    made = r["cache_made_in_place"]
    assert made and all(made), made
