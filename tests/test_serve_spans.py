"""The serve loop's own tracing, in the inline, overlap and meshed modes.

- Host phase spans (`serve.<phase>`, `jax.profiler.TraceAnnotation`)
  tile `serve()`: they never overlap, leave no gap of 1 ms or more, and
  each chunk has one upload, dispatch and readback.
- `ServeReport.chunks` counts what was served: decode lane-steps are
  the tokens after each first token, prompt tokens consumed are the
  prompts, admissions and releases are the requests.
- `Request.first_token_read_at` is the readback of the chunk that
  delivered the first token.
- Each chunk record's dispatch and readback seconds are its spans'
  durations.
- The compiled serve chunk carries the named scopes by which a trace's
  device time can be summed.

The meshed mode runs on a 1x1 mesh, which takes the sharded serve path
on one device.
"""

import gc
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.launch.mesh import make_test_mesh
from repro.models.model import Model
from repro.serving.engine import (PHASES, SCOPES, EngineConfig,
                                  ServingEngine)
from repro.serving.scheduler import Request

SLOTS, STRIDE = 2, 4
#: the largest host time between two spans (or at serve()'s ends)
GAP_NS = 1_000_000
#: traced serves a tiling check may take: a loaded host can deschedule
#: the thread for milliseconds between two spans, which is no host
#: work; a statement left outside every span leaves its gap every time
TILING_TRIES = 3


@pytest.fixture(scope="module")
def model_params():
    model = Model(configs.get_smoke("internlm2-1.8b"))
    return model, model.init(jax.random.key(0))


def _requests(vocab):
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, vocab, (20 + 9 * i,)),
                    max_new_tokens=3 + 2 * i) for i in range(5)]


def _host_spans(path):
    """(name, start_ns, end_ns) of the host's spans. Every thread's line
    is read: the main thread's is named after the executable
    (`python`, `python3`)."""
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [(e.name, int(e.start_ns),
                       int(e.start_ns + e.duration_ns))
                      for e in line.events]
    return sorted(spans, key=lambda s: s[1])


def _traced_serve(eng, vocab, out):
    """Serve five requests under the profiler (the benchmark's options:
    host spans, no Python function tracer): the report and the host's
    spans, `test.serve` around the call."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    reqs = _requests(vocab)
    gc.disable()                # a collection is host time in no span
    try:
        with jax.profiler.trace(out, profiler_options=opts):
            with jax.profiler.TraceAnnotation("test.serve"):
                report = eng.serve(reqs, num_slots=SLOTS, seed=1)
    finally:
        gc.enable()
    path = glob.glob(out + "/**/*.xplane.pb", recursive=True)[0]
    return report, _host_spans(path)


@pytest.fixture(scope="module", params=["inline", "overlap", "meshed"])
def served(request, model_params, tmp_path_factory):
    """One traced serve of five requests in a mode: the engine, the
    report, the host spans, and the compiled serve chunk's text."""
    model, params = model_params
    mesh = make_test_mesh(1, 1) if request.param == "meshed" else None
    eng = ServingEngine(model, params, EngineConfig(
        max_context=160, telemetry_stride=STRIDE, prefill_chunk=16,
        overlap_migrations=request.param == "overlap"), mesh=mesh)
    report, spans = _traced_serve(eng, model.cfg.vocab,
                                  str(tmp_path_factory.mktemp("trace")))
    hlo = eng._serve_jit.lower(
        *eng.serve_chunk_shapes(SLOTS)).compile().as_text()
    return eng, report, spans, hlo


def _widest_gap(spans):
    """Checks the phase spans' order and counts; returns the widest host
    time, ns, that they leave uncovered inside `test.serve`."""
    (_, lo, hi), = [s for s in spans if s[0] == "test.serve"]
    phases = [s for s in spans if s[0].startswith("serve.")]
    assert {n[len("serve."):] for n, _, _ in phases} <= set(PHASES)
    assert phases[0][0] == "serve.setup"
    assert phases[-1][0] == "serve.report"
    gaps = [phases[0][1] - lo, hi - phases[-1][2]]
    for (a, _, end), (b, start, _) in zip(phases, phases[1:]):
        assert start >= end, (a, b)                  # no overlap
        gaps.append(start - end)
    return max(gaps), phases


def test_phase_spans_tile_serve(served, model_params, tmp_path):
    eng, report, spans, _ = served
    widest, phases = _widest_gap(spans)
    n = len(report.chunks)
    assert n >= 3
    for name in ("upload", "dispatch", "readback"):
        assert sum(s[0] == "serve." + name for s in phases) == n
    for i in range(TILING_TRIES - 1):
        if widest < GAP_NS:
            break
        widest = _widest_gap(_traced_serve(
            eng, model_params[0].cfg.vocab, str(tmp_path / str(i)))[1])[0]
    assert widest < GAP_NS, widest


def test_chunk_records_time_the_spans(served):
    """A chunk's `phase_s` dispatch and readback are the durations of
    its `serve.dispatch` and `serve.readback` spans: the records and
    the trace time the same intervals."""
    _, report, spans, _ = served
    for name in ("dispatch", "readback"):
        got = [c.phase_s[name] for c in report.chunks]
        want = [1e-9 * (b - a) for n, a, b in spans if n == "serve." + name]
        assert got == pytest.approx(want, rel=0.05, abs=1e-3)


def test_chunk_records_count_what_was_served(served):
    _, report, _, _ = served
    reqs = report.completed
    assert [r.status for r in reqs] == ["ok"] * 5
    chunks = report.chunks
    for c in chunks:
        assert c.rids.shape == (SLOTS,)
        for v in (c.stamps, c.decoding, c.prefilling, c.prompt_tokens):
            assert v.shape == (STRIDE,)
        assert (c.decoding + c.prefilling <= SLOTS).all()
        assert c.t_dispatch <= c.t_ready
        assert (np.diff(c.stamps) > 0).all()
        assert set(c.phase_s) <= set(PHASES)
        assert {"upload", "dispatch", "readback"} <= set(c.phase_s)
        assert all(v >= 0 for v in c.phase_s.values())
    assert "setup" in chunks[0].phase_s
    assert all("setup" not in c.phase_s for c in chunks[1:])
    decoded = sum(int(c.decoding.sum()) for c in chunks)
    assert decoded == sum(len(r.output) - 1 for r in reqs)
    consumed = sum(int(c.prompt_tokens.sum()) for c in chunks)
    assert consumed == sum(r.prefilled for r in reqs) \
        == sum(r.prompt_len for r in reqs)
    assert sum(c.admitted for c in chunks) == len(reqs)
    assert sum(c.released for c in chunks) == len(reqs)
    assert chunks[0].queue_depth == len(reqs) - SLOTS
    assert chunks[-1].queue_depth == 0
    # a lane's request is on the records of the chunks it ran in
    for r in reqs:
        assert any(r.rid in c.rids for c in chunks)


def test_first_token_read_at_is_its_chunks_readback(served):
    _, report, _, _ = served
    for r in report.completed:
        (c,) = [c for c in report.chunks if r.first_token_at in c.stamps]
        assert r.first_token_read_at == c.t_ready
        assert r.finished_at in [s for c in report.chunks
                                 for s in c.stamps]


def test_compiled_chunk_names_its_scopes(served):
    _, _, _, hlo = served
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    found = {p for path in paths for p in path.split("/")}
    assert set(SCOPES) <= found, set(SCOPES) - found


def test_serve_cli_summarises_the_boundaries(served):
    """`launch.serve` prints one boundary summary from the records."""
    from repro.launch.serve import boundary_summary
    _, report, _, _ = served
    chunks = report.chunks
    b = boundary_summary(chunks)
    assert b["chunks"] == len(chunks)
    assert b["setup_ms"] == pytest.approx(1e3 * chunks[0].phase_s["setup"])
    assert list(b["host_ms"])[:4] == ["admit", "upload", "dispatch",
                                      "readback"]
    assert b["host_ms"]["dispatch"] == pytest.approx(
        1e3 * np.mean([c.phase_s["dispatch"] for c in chunks]))
    # busy lane-steps: one per decoded token, and one per prompt slice
    # of `prefill_chunk` (16) tokens, the last one short
    busy = sum(len(r.output) - 1 + -(-r.prompt_len // 16)
               for r in report.completed)
    assert b["lane_occupancy"] == pytest.approx(
        100.0 * busy / (len(chunks) * STRIDE * SLOTS))
    assert 0 < b["prefill_step_share"] < 100
    steps = len(chunks) * STRIDE
    assert b["step_ms"] == pytest.approx(
        1e3 * sum(c.t_ready - c.t_dispatch for c in chunks) / steps)
    assert b["prompt_tokens_per_step"] == pytest.approx(
        sum(r.prompt_len for r in report.completed) / steps)
    assert b["admitted_per_chunk"] == pytest.approx(5 / len(chunks))
    assert b["released_per_chunk"] == pytest.approx(5 / len(chunks))
    assert b["queue_depth_max"] == 5 - SLOTS
    assert b["queue_depth_mean"] == pytest.approx(
        np.mean([c.queue_depth for c in chunks]))
    assert boundary_summary([]) == {}
