"""Live-telemetry -> simulator bridge (repro.serving.trace_bridge).

The load-bearing pin: live static placement and SIMULATED static
placement are the same deterministic rule, so pricing the captured
stream and replaying it through the simulator must agree to float
tolerance — that equality is what makes the reported bound fraction a
number, not a vibe.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                os.pardir, "benchmarks"))
import workloads as wl                                   # noqa: E402
from repro import configs
from repro.core.placement.base import HBM, UNALLOC
from repro.core.sa import SAConfig
from repro.core.tiers import GH200
from repro.models.model import Model
from repro.serving import trace_bridge
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import Request, TERMINAL_STATUSES
from repro.serving.slo import SLOPolicy

STEPS = 24
PROMPT = 272          # spills past the 16-page HBM pool (ctx 512)
SA_CFG = SAConfig(max_evaluations=12, iters_per_level=4, seed=0)


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get_smoke("internlm2-1.8b")
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


def _drive(model, params, policy):
    eng = ServingEngine(model, params, EngineConfig(
        max_context=512, hbm_fraction=0.25, policy=policy,
        attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=8, trace_telemetry=True))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, model.cfg.vocab, (1, PROMPT)),
                          jnp.int32)
    eng.start(prompts)
    eng.generate(jnp.array([1], jnp.int32), STEPS)
    return eng, trace_bridge.collect(eng)


@pytest.fixture(scope="module")
def static_rec(dense_model):
    return _drive(*dense_model, "static")


@pytest.fixture(scope="module")
def importance_rec(dense_model):
    return _drive(*dense_model, "importance")


class TestRecord:
    def test_shapes_and_codes(self, static_rec):
        eng, rec = static_rec
        L = eng.geo.num_layers
        P = eng.geo.max_pages
        assert rec.access.shape == (STEPS, L, P)
        assert rec.tier.shape == (STEPS, L, P)
        assert rec.moves.shape == (STEPS, 2)
        assert set(np.unique(rec.tier)) <= {UNALLOC, 0, 1}
        # a page is only ever read while it exists
        assert not np.any(rec.access & (rec.tier == UNALLOC))

    def test_pages_exist_monotonically(self, static_rec):
        _, rec = static_rec
        exists = rec.tier != UNALLOC
        assert np.all(exists[1:] >= exists[:-1])

    def test_layer_trace_roundtrip(self, static_rec):
        eng, rec = static_rec
        for layer in range(rec.num_layers):
            tr = trace_bridge.layer_trace(rec, layer)   # .validate()s
            prompt_pages = -(-PROMPT // rec.page_tokens)
            assert np.all(tr.page_born[:prompt_pages] == 0)
            assert tr.prompt_len == PROMPT
            assert tr.decode_len == STEPS
            assert 0.0 < tr.sparsity < 1.0

    def test_migration_counts_match_planner_telemetry(self,
                                                      importance_rec):
        """Tier transitions must recover exactly the promote counts the
        planner reported (batch 1; the final step's moves are
        unobservable by construction)."""
        _, rec = importance_rec
        m_in = np.zeros(rec.num_steps, np.int64)
        for layer in range(rec.num_layers):
            p, _ = trace_bridge.layer_migrations(rec, layer)
            m_in += p
        np.testing.assert_array_equal(m_in[:-1], rec.moves[:-1, 0])
        assert rec.moves.sum() > 0      # the stream actually migrated

    def test_collect_without_capture_raises(self, dense_model):
        model, params = dense_model
        eng = ServingEngine(model, params, EngineConfig(policy="static"))
        with pytest.raises(ValueError, match="trace_telemetry"):
            trace_bridge.collect(eng)

    def test_collect_serve_without_capture_raises(self, dense_model):
        """serve() accepts capture since PR 5; collecting a stream that
        never captured still fails loudly."""
        model, params = dense_model
        eng = ServingEngine(model, params, EngineConfig(policy="static"))
        eng.serve([Request(rid=0, prompt=np.arange(8),
                           max_new_tokens=2)])
        with pytest.raises(ValueError, match="trace_telemetry"):
            trace_bridge.collect_serve(eng)


class TestScoring:
    def test_live_static_equals_simulated_static(self, static_rec):
        """The bridge's self-test: same placement rule, same access
        pattern, same cost model -> same number."""
        _, rec = static_rec
        score = trace_bridge.score_headroom(rec, GH200, oracles=())
        assert score["live_total_s"] > 0
        assert score["headroom_vs_static"] == pytest.approx(1.0,
                                                            rel=1e-9)

    def test_hit_fraction_counts_hbm_reads(self, static_rec):
        _, rec = static_rec
        frac = trace_bridge.hit_fraction(rec)
        assert 0.0 < frac < 1.0
        hits = int((rec.access & (rec.tier == HBM)).sum())
        assert frac == pytest.approx(hits / int(rec.access.sum()))

    def test_dynamic_policy_beats_static_and_bound_holds(
            self, static_rec, importance_rec):
        _, srec = static_rec
        _, irec = importance_rec
        s = trace_bridge.score_headroom(srec, GH200, sa_cfg=SA_CFG)
        i = trace_bridge.score_headroom(irec, GH200, sa_cfg=SA_CFG)
        # the deployable policy converts host reads into HBM hits
        assert i["live_hit_fraction"] > s["live_hit_fraction"]
        assert i["live_total_s"] < s["live_total_s"]
        # the SA oracle lower-bounds (faster-than) both live streams'
        # static baseline, and the bound fraction is a sane ratio
        assert i["sa_total_s"] <= i["static_total_s"] * 1.001
        assert 0.0 < s["bound_fraction"] <= 1.001
        assert s["bound_fraction"] < i["bound_fraction"] <= 1.2


def test_importance_goodput_at_least_static(dense_model):
    """The goodput-under-SLO curve of a contended, sampled mixed
    Poisson+bursty stream: importance placement turns its headroom into
    goodput at least as well as static at equal targets. Latency is the
    MODELED per-request latency, and the TPOT target is static's median,
    so the comparison is deterministic: both policies face one contract
    and scale 1.0 sits at static's half-good point."""
    model, params = dense_model
    sa_cfg = SAConfig(max_evaluations=8, iters_per_level=3, seed=0)
    mixed = wl.mixed_stream(
        101, 6, rate_rps=8.0, len_mu=5.6, len_sigma=0.08, zipf_frac=0.1,
        min_prompt=192, max_prompt=288, page_tokens=16, snap_frac=0.5,
        out_mu=2.0, out_sigma=0.4, max_new=10, vocab=model.cfg.vocab,
        temperature=0.7)
    goodput, contract = {}, None
    for policy in ("static", "importance"):
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy=policy,
            attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
            telemetry_stride=8, prefill_chunk=16, prefill_budget=24,
            eos_id=model.cfg.eos_id, trace_telemetry=True))
        rep = eng.serve(mixed.requests(open_loop=False), num_slots=2,
                        **mixed.serve_kwargs())
        assert all(s in TERMINAL_STATUSES for s in rep.statuses.values())
        rec = trace_bridge.collect_serve(eng)
        if contract is None:
            scored = trace_bridge.score_serve(rec, GH200, oracles=())
            tpots = sorted(sc["live_total_s"] / sc["steps"]
                           for sc in scored["requests"].values()
                           if sc["steps"])
            contract = SLOPolicy.uniform(
                ttft_s=300.0, tpot_s=tpots[len(tpots) // 2])
        out = trace_bridge.goodput_curve(rec, GH200, rep, contract,
                                         sa_cfg=sa_cfg)
        assert 0.0 < out["aggregate"]["bound_fraction"] <= 1.2
        goodput[policy] = np.mean([c["goodput"] for c in out["curve"]])
    assert goodput["importance"] >= goodput["static"], goodput
