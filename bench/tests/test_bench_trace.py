"""The reduction from a profiler trace to busy, idle, kernel, collective
and gap times: by hand on small interval sets, and on a trace recorded
on a TPU v5e (a smoke-size decode-spill window, 4 lanes, stride 8;
`data/serve_smoke.xplane.pb.gz`)."""

import gzip
import os
import shutil

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "serve_smoke.xplane.pb.gz")


def test_merge_and_complement():
    busy = tr._merge([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert busy == [(0, 4), (5, 10)]
    assert tr._complement(busy, 0, 12) == [(4, 5), (10, 12)]
    assert tr.covered_ns(busy, 3, 6) == 2


def test_self_time_of_nested_ops():
    """A while loop (0-100) holds two kernels (10-30, 40-50) and a
    fusion (60-90) that holds a copy (70-80)."""
    got = tr._self_times([(0, 100, "while"), (10, 30, "k"), (40, 50, "k"),
                          (60, 90, "fusion"), (70, 80, "copy")])
    assert got == pytest.approx({"while": 40e-9, "k": 30e-9,
                                 "fusion": 20e-9, "copy": 10e-9})


def test_op_names():
    ev = "%paged_attention.14 = (bf16[8,16,128]{2,1,0}, f32[8]) custom-call"
    assert tr.op_name(ev) == "paged_attention.14"
    assert tr.op_kind(ev) == "paged_attention"
    assert tr.op_kind("%all-reduce-start.3 = f32[4] all-reduce-start(x)") \
        == "all-reduce-start"
    assert tr.op_kind("fusion") == "fusion"


def test_gap_labels_take_the_host_span_that_overlaps_most():
    spans = [("bench.serve_chunk", 0, 50), ("np.asarray", 55, 70),
             ("bench.release_lanes", 68, 90)]
    assert tr._label(spans, 50, 80) == "host: np.asarray"
    assert tr._label(spans, 95, 99) == tr.UNLABELLED


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "serve_smoke.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce_trace(str(path), ("paged_attention",),
                           span_prefix="bench.")


def test_recorded_trace_window_and_busy(recorded):
    s = recorded
    assert len(s.devices) == 1 and s.devices[0].name == "/device:TPU:0"
    assert 0 < s.busy_s <= s.window_s
    # self times partition the busy time (nested ops counted once)
    total = sum(s.devices[0].op_self_s.values())
    assert total == pytest.approx(s.busy_s, rel=0.05)


def test_recorded_trace_kernel_and_chunks(recorded):
    s = recorded
    assert 0 < s.kernel_s("paged_attention") < s.busy_s
    assert s.devices[0].collective_s == 0
    idle, n = tr.module_gaps(s.devices[0], "serve_chunk")
    assert n >= 1 and idle >= 0
    names = [k for k, _ in s.top_ops(10)]
    assert any(k.startswith("paged_attention") for k in
               [n for n, _ in s.top_ops(50)]) and names
    gaps = s.idle_gaps()
    assert gaps and all(v > 0 for _, v in gaps)
    assert "host: bench.window" not in dict(gaps)
    assert sum(v for _, v in gaps) <= s.window_s - s.busy_s + 1e-9
