"""The benchmark's traffic generator and how cells are found.

Determinism as in the program's own workload tests: one (spec, seed,
window) is one stream, bitwise. Stratification: every seed gets the
same set of lengths and gaps in another order. And a new cell needs a
traffic file and an entry in BENCHMARK.json, nothing else.
"""

import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

from bench import harness, workloads as wl
from bench.tests import smoke


def _spec(**kw):
    base = dict(loop="open", arrival="poisson", rate_rps=5.0,
                prompt=wl.LengthSpec("lognormal", 16, 640, median=192,
                                     sigma=0.7),
                output=wl.LengthSpec("uniform", 8, 256))
    base.update(kw)
    return wl.TrafficSpec(**base)


def _bitwise(a: wl.Stream, b: wl.Stream) -> bool:
    return (a.arrival_s.tobytes() == b.arrival_s.tobytes()
            and a.prompt_len.tobytes() == b.prompt_len.tobytes()
            and a.max_new.tobytes() == b.max_new.tobytes()
            and all(x.tobytes() == y.tobytes()
                    for x, y in zip(a.prompts, b.prompts)))


@pytest.mark.parametrize("arrival", wl.ARRIVALS)
@pytest.mark.parametrize("loop", ["open", "closed"])
def test_same_seed_same_stream(arrival, loop):
    spec = _spec(arrival=arrival, loop=loop, n_requests=24)
    seed = 2**33 + 17            # run seeds may exceed 32 bits
    assert _bitwise(wl.generate(spec, seed, 20.0, 1000),
                    wl.generate(spec, seed, 20.0, 1000))


def test_different_seed_different_stream():
    a = wl.generate(_spec(), 1, 20.0, 1000)
    b = wl.generate(_spec(), 2, 20.0, 1000)
    assert a.arrival_s.tobytes() != b.arrival_s.tobytes()
    assert a.prompt_len.tobytes() != b.prompt_len.tobytes()


def test_stratified_seeds_share_sizes_and_gaps():
    a = wl.generate(_spec(), 5, 30.0, 1000)
    b = wl.generate(_spec(), 6, 30.0, 1000)
    assert a.n == b.n == 150
    for x, y in ((a.prompt_len, b.prompt_len), (a.max_new, b.max_new),
                 (np.diff(a.arrival_s, prepend=0), np.diff(b.arrival_s,
                                                           prepend=0))):
        np.testing.assert_allclose(np.sort(x), np.sort(y), atol=1e-9)
    assert a.arrival_s[-1] == pytest.approx(30.0 * 150 / 151)
    assert np.all(np.diff(a.arrival_s) > 0)


def test_closed_loop_blocks_share_sizes_across_seeds():
    """Each block of a closed loop holds the same sizes on every seed,
    and the blocks together are the whole stratified law."""
    spec = _spec(loop="closed", n_requests=64, block=8,
                 prompt=wl.LengthSpec("uniform", 2048, 3584))
    a = wl.generate(spec, 5, 30.0, 1000)
    b = wl.generate(spec, 2**40 + 3, 30.0, 1000)
    assert a.prompt_len.tobytes() != b.prompt_len.tobytes()
    for k in range(8):
        blk = slice(8 * k, 8 * k + 8)
        for x, y in ((a.prompt_len, b.prompt_len), (a.max_new, b.max_new)):
            np.testing.assert_array_equal(np.sort(x[blk]), np.sort(y[blk]))
        assert a.prompt_len[blk].min() < 2048 + 192
        assert a.prompt_len[blk].max() > 3584 - 192
    whole = wl.generate(dataclasses.replace(spec, block=0), 5, 30.0, 1000)
    np.testing.assert_array_equal(np.sort(a.prompt_len),
                                  np.sort(whole.prompt_len))
    with pytest.raises(ValueError):
        _spec(loop="closed", n_requests=64, block=7)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_trace_seed_replays_sizes_and_arrivals(loop):
    """With `trace_seed` every seed serves the same sizes at the same
    times; only the prompt tokens follow the run's seed."""
    spec = _spec(loop=loop, n_requests=16, trace_seed=12)
    a = wl.generate(spec, 1, 30.0, 1000)
    b = wl.generate(spec, 2**40 + 1, 30.0, 1000)
    for x, y in ((a.arrival_s, b.arrival_s), (a.prompt_len, b.prompt_len),
                 (a.max_new, b.max_new)):
        assert x.tobytes() == y.tobytes()
    assert any(x.tobytes() != y.tobytes()
               for x, y in zip(a.prompts, b.prompts))
    other = wl.generate(dataclasses.replace(spec, trace_seed=13), 1, 30.0,
                        1000)
    assert other.prompt_len.tobytes() != a.prompt_len.tobytes()


def test_a_staggered_loop_cuts_only_its_first_block():
    """Request k of the first block keeps (k + 0.5) / block of its
    answer; every other request is drawn as without the stagger."""
    spec = _spec(loop="closed", n_requests=16, block=8, trace_seed=3)
    plain = wl.generate(spec, 5, 30.0, 1000)
    cut = wl.generate(dataclasses.replace(spec, staggered=True), 5, 30.0,
                      1000)
    want = np.maximum(np.rint(plain.max_new[:8] * (np.arange(8) + 0.5) / 8),
                      1)
    np.testing.assert_array_equal(cut.max_new[:8], want)
    np.testing.assert_array_equal(cut.max_new[8:], plain.max_new[8:])
    assert cut.prompt_len.tobytes() == plain.prompt_len.tobytes()
    with pytest.raises(ValueError):
        _spec(loop="closed", n_requests=16, staggered=True)


def test_lengths_follow_their_law():
    spec = _spec()
    s = wl.generate(spec, 9, 200.0, 1000)
    assert s.prompt_len.min() >= 16 and s.prompt_len.max() <= 640
    assert np.median(s.prompt_len) == pytest.approx(192, abs=2)
    assert s.max_new.min() >= 8 and s.max_new.max() <= 256


def test_closed_loop_is_due_at_once_and_capped():
    spec = _spec(loop="closed", n_requests=12,
                 prompt=wl.LengthSpec("uniform", 100, 200),
                 output=wl.LengthSpec("uniform", 50, 90))
    s = wl.generate(spec, 4, 30.0, 1000, limit=240)
    assert s.n == 12 and not s.arrival_s.any()
    assert np.all(s.prompt_len + s.max_new <= 240)
    assert all(len(p) == n for p, n in zip(s.prompts, s.prompt_len))


def test_every_traffic_file_parses():
    d = os.path.join(harness.BENCH, "traffic")
    for f in os.listdir(d):
        t = harness.load_json(d, f)
        wl.TrafficSpec.from_json(t["traffic"])
        assert t["engine"]["lanes"] >= 1 and t["why"]


def test_a_new_cell_is_a_traffic_file_and_an_entry(tmp_path):
    """Copy the benchmark, add one traffic file and one workload entry:
    the harness runs the new cell unchanged."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    traffic = smoke.closed_traffic(harness.load_json(
        harness.BENCH, "traffic", "decode-spill.json"), n=6)
    traffic["why"] = "a mix added by data alone"
    with open(root / "bench" / "traffic" / "tiny-mix.json", "w") as f:
        json.dump(traffic, f)
    bench["workloads"].append({
        "name": "internlm2-1.8b.tiny-mix", "config": "internlm2-1.8b",
        "traffic": "tiny-mix", "chips": 1, "why": "test"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell, conf, got, _ = harness.load_cell("internlm2-1.8b.tiny-mix",
                                           str(root))
    assert got["why"] == "a mix added by data alone"
    r = smoke.run("internlm2-1.8b.tiny-mix", root=str(root),
                  config=smoke.config(conf), traffic=got)
    assert r["correct"] and r["attempted"] == 6
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}
