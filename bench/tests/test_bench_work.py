"""FLOPs and bytes the traffic needs, against hand-computed values, and
the paged kernel's work counted from the traffic alone."""

import numpy as np
import pytest

from bench import harness, work
from bench.tests import smoke

D = work.Dims(layers=2, d_model=4, heads=2, kv_heads=1, head_dim=2, d_ff=8,
              vocab=10)


def test_sizes_by_hand():
    assert D.layer_params == 16 + 16 + 16 + 96
    assert D.step_weight_bytes == (2 * (144 + 8) + 4 + 40) * 2
    assert D.kv_token_bytes == 2 * 2 * 1 * 2 * 2
    assert D.head_flops == 80
    assert float(D.attn_flops(6)) == 4 * 2 * 2 * 2 * 6
    assert list(D.live_pages([1, 16, 17])) == [1, 1, 2]


def test_chunk_work_by_hand():
    """Lane 0 decodes at context 5 then 6; lane 1 prefills 3 then 2
    prompt tokens and samples its first token."""
    rec = work.ChunkRecord(
        length0=np.array([5, 0]),
        emitted=np.array([[7, -1], [8, -1]]),
        first=np.array([[-1, -1], [-1, 4]]),
        prefill=np.array([[0, 3], [0, 2]]))
    w = work.chunk_work(D, rec)
    assert (w.decode_tokens, w.prefill_tokens) == (2, 5)
    assert (w.model_steps, w.decode_steps) == (2, 2)
    assert w.kernel_flops == 192 + 224
    assert w.kernel_bytes == 2 * 16 * 16
    assert w.step_bytes == 2 * 696 + 4 * 256
    assert w.model_flops == (848 + 1728 + 192) + (880 + 1152 + 288 + 80)


def test_a_window_takes_steps_by_stamp():
    """The chunk spans 0-2 s, so its two steps are stamped 1 and 2 s: a
    window (1, 2] holds the second step alone, at the lengths the first
    left behind."""
    rec = work.ChunkRecord(
        length0=np.array([5, 0]),
        emitted=np.array([[7, -1], [8, -1]]),
        first=np.array([[-1, -1], [-1, 4]]),
        prefill=np.array([[0, 3], [0, 2]]), t0=0.0, t1=2.0)
    w = work.chunk_work(D, rec, 1.0, 2.0)
    assert (w.decode_tokens, w.prefill_tokens, w.model_steps) == (1, 2, 1)
    assert w.kernel_flops == 224
    assert (rec.tokens(1.0, 2.0), rec.tokens(None, 1.0), rec.tokens()) \
        == (2, 1, 3)


def test_idle_steps_cost_nothing():
    rec = work.ChunkRecord(length0=np.array([9]), emitted=np.array([[-1]]),
                           first=np.array([[-1]]), prefill=np.array([[0]]))
    assert work.chunk_work(D, rec) == work.Work()


def _served_work(max_context):
    base = harness.load_cell(smoke.CELL)
    s = harness.Session(
        smoke.CELL, 21, config=smoke.config(base[1]),
        traffic=smoke.closed_traffic(base[2], max_context=max_context, n=6),
        peaks=smoke.PEAKS)
    served = s.window(s.stream(21, 60.0), 21, 60.0)
    assert all(r["status"] == "ok" for r in served.requests)
    return work.total_work(s.dims, served.records), s.sut.geo


def test_kernel_work_ignores_pool_size_and_holes():
    """The same requests served with a 2x larger pool per lane (twice
    the holes the kernel streams) need the same kernel work."""
    (a, ga), (b, gb) = _served_work(256), _served_work(512)
    assert gb.max_pages > ga.max_pages
    assert a.kernel_bytes > 0
    assert (a.kernel_flops, a.kernel_bytes) == (b.kernel_flops,
                                                b.kernel_bytes)
    assert a.decode_tokens == b.decode_tokens > 0
    assert a.model_flops == pytest.approx(b.model_flops)
