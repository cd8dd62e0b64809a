"""The pluggable device-policy plane (repro.serving.policies).

Mechanical invariants every registered policy must satisfy to ride the
fused serve loop — fixed plan capacity, per-lane active gating, owner
consistency through `apply_migrations`, zero retraces on state-value
changes — plus the bitwise pin that `importance` IS the planner the
engine shipped with, and the one-executable-per-policy serve-stream
assert.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.placement import POLICIES
from repro.core.tiers import GH200
from repro.kvcache.migrate import apply_migrations
from repro.kvcache.paged import CacheGeometry, prefill_cache
from repro.models.model import Model
from repro.serving import control
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.policies import make_policy, policy_names
from repro.serving.scheduler import Request

BUDGET = 2


def _geo():
    return CacheGeometry(num_layers=2, batch=2, page_tokens=4,
                         hbm_pages=2, host_pages=6, kv_heads=2,
                         head_dim=8, dtype=jnp.float32)


def _cfg(policy="importance"):
    return EngineConfig(policy=policy, attention_sparsity=0.5,
                        promote_thresh=0.02, migration_budget_frac=1.0,
                        spec=GH200)


def _cache():
    """Seven alive pages (2 HBM, 5 host) with an importance profile
    that makes every dynamic policy want at least one move: page 1
    (HBM) is cold and outside the Quest mask; pages 2 and 6 (host) are
    hot / recent."""
    geo = _geo()
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.standard_normal((2, 2, 28, 2, 8)), jnp.float32)
    cache = prefill_cache(geo, kv, kv, 28)
    imp = np.tile(np.asarray(
        [0.5, 0.01, 0.9, 0.3, 0.05, 0.02, 0.2, 0.0], np.float32),
        (2, 2, 1))
    return geo, dataclasses.replace(cache, importance=jnp.asarray(imp))


def assert_owner_consistent(cache):
    """page_table and the two owner maps must stay a bijection."""
    pt = np.asarray(cache.page_table)
    ho = np.asarray(cache.hbm_owner)
    eo = np.asarray(cache.host_owner)
    L, B, _ = pt.shape
    hbm = ho.shape[2]
    for l in range(L):
        for b in range(B):
            for s, page in enumerate(ho[l, b]):
                if page >= 0:
                    assert pt[l, b, page] == s, (l, b, s, page)
            for s, page in enumerate(eo[l, b]):
                if page >= 0:
                    assert pt[l, b, page] == hbm + s, (l, b, s, page)
            for page, slot in enumerate(pt[l, b]):
                if slot >= 0:
                    if slot < hbm:
                        assert ho[l, b, slot] == page, (l, b, page, slot)
                    else:
                        assert eo[l, b, slot - hbm] == page, \
                            (l, b, page, slot)
            owned = [p for p in ho[l, b] if p >= 0] + \
                [p for p in eo[l, b] if p >= 0]
            assert len(owned) == len(set(owned)), (l, b, owned)


@pytest.mark.parametrize("name", policy_names())
class TestPolicyInvariants:
    def test_plan_capacity_is_geometry_constant(self, name):
        geo, cache = _cache()
        pol = make_policy(name, cfg=_cfg(name), geo=geo)
        plan, _, (n_pro, n_dem) = pol.plan(
            cache, pol.init_state(geo), None, BUDGET)
        capacity = geo.num_layers * geo.batch * BUDGET
        for field in dataclasses.fields(plan):
            assert getattr(plan, field.name).shape == (capacity,), \
                field.name
        got_pro, got_dem = plan.row_counts()
        assert int(got_pro) == int(n_pro) <= capacity
        assert int(got_dem) == int(n_dem) <= capacity
        assert int(n_dem) <= int(n_pro)      # demotes pair with promotes

    def test_inactive_lanes_plan_zero_moves(self, name):
        geo, cache = _cache()
        pol = make_policy(name, cfg=_cfg(name), geo=geo)
        active = jnp.asarray([True, False])
        plan, _, _ = pol.plan(cache, pol.init_state(geo), active, BUDGET)
        for rows in (plan.pro_batch, plan.dem_batch):
            rows = np.asarray(rows)
            assert not np.any(rows == 1), (name, rows)

    def test_owner_maps_consistent_through_apply(self, name):
        geo, cache = _cache()
        pol = make_policy(name, cfg=_cfg(name), geo=geo)
        state = pol.init_state(geo)
        for _ in range(3):
            plan, state, _ = pol.plan(cache, state, None, BUDGET)
            cache = apply_migrations(cache, plan)
            assert_owner_consistent(cache)

    def test_zero_retraces_on_state_value_changes(self, name):
        geo, cache = _cache()
        pol = make_policy(name, cfg=_cfg(name), geo=geo)

        @jax.jit
        def planner(cache, state):
            return pol.plan(cache, state, None, BUDGET)

        state = pol.init_state(geo)
        _, state, _ = planner(cache, state)
        bumped = jax.tree.map(lambda x: x + 1, state)
        hotter = dataclasses.replace(
            cache, importance=cache.importance * 0.5 + 0.1)
        planner(hotter, bumped)
        assert planner._cache_size() == 1


class TestPolicyBehaviour:
    def test_every_dynamic_policy_plans_a_move(self):
        """The fixture cache is built so each dynamic policy has at
        least one profitable move — a policy that never migrates
        under these conditions is wired wrong."""
        geo, cache = _cache()
        for name in policy_names():
            if name == "static":
                continue
            pol = make_policy(name, cfg=_cfg(name), geo=geo)
            _, _, (n_pro, _) = pol.plan(
                cache, pol.init_state(geo), None, BUDGET)
            assert int(n_pro) >= 1, name

    def test_static_plans_nothing(self):
        geo, cache = _cache()
        pol = make_policy("static", cfg=_cfg("static"), geo=geo)
        plan, _, (n_pro, n_dem) = pol.plan(
            cache, pol.init_state(geo), None, BUDGET)
        assert int(n_pro) == 0 and int(n_dem) == 0
        assert np.all(np.asarray(plan.pro_layer) == -1)
        # applying the empty plan is a bitwise no-op
        after = apply_migrations(cache, plan)
        for field in dataclasses.fields(cache):
            np.testing.assert_array_equal(
                np.asarray(getattr(cache, field.name)),
                np.asarray(getattr(after, field.name)))

    def test_importance_policy_is_plan_migrations(self):
        """Bitwise pin: the extracted `importance` policy reproduces
        `control.plan_migrations` row for row."""
        geo, cache = _cache()
        cfg = _cfg("importance")
        pol = make_policy("importance", cfg=cfg, geo=geo)
        for active in (None, jnp.asarray([True, False])):
            got, _, (g_pro, g_dem) = pol.plan(
                cache, pol.init_state(geo), active, BUDGET)
            want, w_pro, w_dem = control.plan_migrations(
                cache, budget=BUDGET,
                promote_thresh=cfg.promote_thresh, active=active)
            for field in dataclasses.fields(want):
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, field.name)),
                    np.asarray(getattr(want, field.name)))
            assert int(g_pro) == int(w_pro)
            assert int(g_dem) == int(w_dem)

    def test_cost_aware_threshold_scales_with_link(self):
        """A harsher link (TPU PCIe vs GH200 NVLink-C2C) must raise
        the promote bar."""
        from repro.core.placement.cost_aware import payback_threshold
        from repro.core.tiers import TPU_V5E
        assert payback_threshold(TPU_V5E, 4.0) > \
            payback_threshold(GH200, 4.0)

    def test_sim_policies_name_live_counterparts(self):
        """Cross-layer interface: every simulator policy that claims a
        live mirror must point at a registered device policy."""
        mirrored = {cls.device_counterpart
                    for cls in POLICIES.values()
                    if cls.device_counterpart is not None}
        assert mirrored <= set(policy_names()), mirrored
        assert {"static", "recency", "cost_aware", "quest"} <= mirrored

    def test_unknown_policy_rejected_at_construction(self):
        cfg = configs.get_smoke("internlm2-1.8b")
        model = Model(cfg)
        with pytest.raises(ValueError, match="importance"):
            ServingEngine(model, None, EngineConfig(policy="lru"))


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get_smoke("internlm2-1.8b")
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


@pytest.mark.parametrize("name", policy_names())
def test_serve_stream_one_executable_per_policy(dense_model, name):
    """Acceptance pin: every registered policy drives the FULL serve
    stream — mixed prompt lengths, admissions, completions — on ONE
    compiled executable."""
    model, params = dense_model
    eng = ServingEngine(model, params, EngineConfig(
        max_context=128, hbm_fraction=0.25, policy=name,
        attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=4, prefill_chunk=16))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, model.cfg.vocab,
                                        (16 + 16 * (i % 2),)),
                    max_new_tokens=3 + i)
            for i in range(3)]
    report = eng.serve(reqs, num_slots=2, seed=0)
    assert len(report) == 3
    assert all(len(r.output) == r.max_new_tokens for r in report)
    assert eng._serve_jit._cache_size() == 1
    assert eng.batcher.free_pages == eng.batcher.total_pages


@pytest.mark.parametrize("name", policy_names())
def test_generate_stream_one_executable_per_policy(dense_model, name):
    """Every registered policy drives a multi-chunk fused `generate`
    stream, with trace capture on, on ONE `_gen_jit` executable: a
    second stream from a fresh `start` reuses it. The prompt spills
    past the HBM pool and Quest sparsity concentrates the read set, so
    every policy but static moves pages."""
    model, params = dense_model
    eng = ServingEngine(model, params, EngineConfig(
        max_context=512, hbm_fraction=0.25, policy=name,
        attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=4, trace_telemetry=True))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, model.cfg.vocab, (1, 272)),
                          jnp.int32)
    for _ in range(2):
        eng.start(prompts)
        assert int(np.asarray((eng._cache.host_owner >= 0).sum())) > 0
        out = eng.generate(jnp.array([1], jnp.int32), 12)
        assert out.shape == (12, 1)
        moved = sum(s.m_in + s.m_out for s in eng.stats)
        assert (moved > 0) == (name != "static")
    assert eng._gen_jit._cache_size() == 1
