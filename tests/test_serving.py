"""Serving engine + continuous batcher behaviour."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.tiers import GH200
from repro.models.model import Model
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get_smoke("internlm2-1.8b")
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


class TestEngine:
    def _drive(self, model, params, policy, steps=10, sparsity=0.5):
        eng = ServingEngine(model, params, EngineConfig(
            max_context=128, hbm_fraction=0.25, policy=policy,
            attention_sparsity=sparsity, spec=GH200))
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(0, model.cfg.vocab, (2, 32)), jnp.int32)
        eng.start(prompts)
        tok = jnp.array([1, 2], jnp.int32)
        for _ in range(steps):
            lg = eng.step(tok)
            assert lg.shape == (2, model.cfg.vocab)
            assert np.isfinite(np.asarray(lg, np.float32)).all()
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        return eng

    def test_static_policy_never_migrates(self, dense_model):
        eng = self._drive(*dense_model, policy="static")
        assert eng.summary()["migrated_bytes"] == 0.0

    def test_importance_policy_stats(self, dense_model):
        eng = self._drive(*dense_model, policy="importance")
        s = eng.summary()
        assert s["steps"] == 10
        assert 0.0 <= s["mean_hbm_hit_rate"] <= 1.0
        assert s["modeled_tokens_per_s"] > 0

    def test_migration_budget_respected(self, dense_model):
        model, params = dense_model
        cfg = EngineConfig(max_context=128, hbm_fraction=0.25,
                           policy="importance", attention_sparsity=0.0,
                           migration_budget_frac=0.05, spec=GH200)
        eng = ServingEngine(model, params, cfg)
        rng = np.random.default_rng(1)
        prompts = jnp.asarray(
            rng.integers(0, model.cfg.vocab, (2, 48)), jnp.int32)
        eng.start(prompts)
        budget_pages = max(1, int(0.05 * eng.geo.hbm_pages))
        tok = jnp.array([1, 2], jnp.int32)
        for _ in range(6):
            eng.step(tok)
        pb = eng.geo.page_bytes()
        L, B = eng.geo.num_layers, eng.geo.batch
        for s in eng.stats:
            assert s.m_in <= budget_pages * pb * L * B


class TestFusedParity:
    """`run`/`generate` (lax.scan fused) vs `step` (eager): identical
    program, so logits must be bitwise equal and StepStats identical."""

    def _engine(self, model, params, policy, sparsity, stride):
        eng = ServingEngine(model, params, EngineConfig(
            max_context=128, hbm_fraction=0.25, policy=policy,
            attention_sparsity=sparsity, spec=GH200,
            promote_thresh=0.005, telemetry_stride=stride))
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(0, model.cfg.vocab, (2, 32)), jnp.int32)
        eng.start(prompts)
        return eng

    @pytest.mark.parametrize("policy,sparsity", [
        ("static", 0.0), ("importance", 0.0), ("importance", 0.5)])
    def test_run_matches_eager_steps(self, dense_model, policy, sparsity):
        model, params = dense_model
        k = 7
        rng = np.random.default_rng(3)
        tokens = jnp.asarray(
            rng.integers(0, model.cfg.vocab, (k, 2)), jnp.int32)

        eager = self._engine(model, params, policy, sparsity, stride=32)
        eager_logits = np.stack(
            [np.asarray(eager.step(tokens[i])) for i in range(k)])
        # stride 3 also exercises the ragged final chunk (3 + 3 + 1)
        for stride in (32, 3):
            fused = self._engine(model, params, policy, sparsity, stride)
            fused_logits = np.asarray(fused.run(tokens))
            np.testing.assert_array_equal(fused_logits, eager_logits)
            assert fused.stats == eager.stats

    def test_generate_matches_eager_greedy(self, dense_model):
        model, params = dense_model
        eager = self._engine(model, params, "importance", 0.4, stride=32)
        tok = jnp.array([1, 2], jnp.int32)
        want = []
        for _ in range(6):
            tok = jnp.argmax(eager.step(tok), -1).astype(jnp.int32)
            want.append(np.asarray(tok))
        fused = self._engine(model, params, "importance", 0.4, stride=4)
        got = np.asarray(fused.generate(jnp.array([1, 2], jnp.int32), 6))
        np.testing.assert_array_equal(got, np.stack(want))
        assert fused.stats == eager.stats

    def test_step_compiles_once_with_live_migrations(self, dense_model):
        """The fused step (control plane + decode + migration) must not
        retrace as promote/demote counts vary across steps. The prompt
        spills past the HBM pool so promotions actually fire."""
        model, params = dense_model
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy="importance",
            attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4))
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(0, model.cfg.vocab, (1, 272)), jnp.int32)
        eng.start(prompts)
        assert int(np.asarray(
            (eng._cache.host_owner >= 0).sum())) > 0   # host tier in use
        tok = jnp.array([1], jnp.int32)
        for _ in range(8):
            tok = jnp.argmax(eng.step(tok), -1).astype(jnp.int32)
        assert eng._step_jit._cache_size() == 1
        assert sum(s.m_in + s.m_out for s in eng.stats) > 0


class TestKernelSlotShare:
    def test_idle_engine_reads_zero(self, dense_model):
        eng = ServingEngine(*dense_model, EngineConfig(
            max_context=128, hbm_fraction=0.25, spec=GH200))
        assert eng.summary()["kernel_slot_share"] == 0.0

    def test_counts_each_steps_resident_slots(self, dense_model):
        """On a short spilling stream the counter is Σ over steps of the
        owner maps' occupied slots, over steps x L x B x (Ph + Pe); the
        kernel's whole-block walk exceeds it by less than one block a
        lane, layer and tier."""
        from repro.kernels.paged_attention import pages_per_block
        model, params = dense_model
        eng = ServingEngine(model, params, EngineConfig(
            max_context=512, hbm_fraction=0.25, policy="importance",
            attention_sparsity=0.0, spec=GH200, promote_thresh=1e-4))
        rng = np.random.default_rng(3)
        eng.start(jnp.asarray(rng.integers(0, model.cfg.vocab, (2, 272)),
                              jnp.int32))
        geo = eng.geo
        T, KH, HD = geo.page_tokens, geo.kv_heads, geo.head_dim
        size = jnp.dtype(geo.dtype).itemsize
        tok = jnp.array([1, 2], jnp.int32)
        occupied = walked = slack = 0
        for _ in range(6):
            tok = jnp.argmax(eng.step(tok), -1).astype(jnp.int32)
            for owner in (eng._cache.hbm_owner, eng._cache.host_owner):
                n = np.asarray((owner >= 0).sum(-1))          # [L, B]
                K = pages_per_block(T, KH, HD, size, owner.shape[-1])
                occupied += int(n.sum())
                walked += int((-(-n // K) * K).sum())
                slack += (K - 1) * n.size
        assert int(np.asarray((eng._cache.host_owner >= 0).sum())) > 0
        slots = 6 * geo.num_layers * geo.batch * geo.max_pages
        share = eng.summary()["kernel_slot_share"]
        assert share == occupied / slots
        assert 0.0 < share < 1.0
        assert occupied <= walked <= occupied + slack


class TestDevicePlanner:
    def test_promotes_hottest_host_page_into_coldest_slot(self):
        from repro.kvcache.paged import CacheGeometry, prefill_cache
        from repro.serving import control

        geo = CacheGeometry(num_layers=1, batch=1, page_tokens=4,
                            hbm_pages=2, host_pages=4, kv_heads=2,
                            head_dim=8, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        kv = jnp.asarray(rng.standard_normal((1, 1, 16, 2, 8)), jnp.float32)
        cache = prefill_cache(geo, kv, kv, 16)   # pages 0,1 hbm; 2,3 host
        # page 3 (host slot 1) is hot; page 0 (hbm slot 0) is coldest
        importance = jnp.asarray([[[0.01, 0.3, 0.02, 0.9]]], jnp.float32)
        cache = dataclasses.replace(cache, importance=importance)
        plan, n_pro, n_dem = control.plan_migrations(
            cache, budget=1, promote_thresh=0.05)
        assert int(n_pro) == 1 and int(n_dem) == 1
        assert int(plan.pro_src[0]) == 1      # host slot of page 3
        assert int(plan.pro_dst[0]) == 0      # coldest hbm slot
        assert int(plan.pro_logical[0]) == 3
        assert int(plan.dem_src[0]) == 0      # victim hbm slot
        assert int(plan.dem_dst[0]) == 1      # vacated host slot
        assert int(plan.dem_logical[0]) == 0

    def test_no_promotion_below_threshold(self):
        from repro.kvcache.paged import CacheGeometry, prefill_cache
        from repro.serving import control

        geo = CacheGeometry(num_layers=1, batch=1, page_tokens=4,
                            hbm_pages=2, host_pages=4, kv_heads=2,
                            head_dim=8, dtype=jnp.float32)
        kv = jnp.zeros((1, 1, 16, 2, 8), jnp.float32)
        cache = prefill_cache(geo, kv, kv, 16)
        plan, n_pro, n_dem = control.plan_migrations(
            cache, budget=2, promote_thresh=0.5)
        assert int(n_pro) == 0 and int(n_dem) == 0
        assert np.all(np.asarray(plan.pro_layer) == -1)


class TestContinuousBatcher:
    def test_admission_and_completion(self):
        cb = ContinuousBatcher(num_slots=2, total_pages=100)
        cb.submit(Request(rid=1, prompt_len=32, max_new_tokens=3))
        cb.submit(Request(rid=2, prompt_len=32, max_new_tokens=5))
        cb.submit(Request(rid=3, prompt_len=32, max_new_tokens=2))
        # slots: r1, r2 admitted; r3 queued
        active = cb.step()
        assert cb.utilization() == 1.0
        for _ in range(10):
            cb.step()
        assert sorted(r.rid for r in cb.completed) == [1, 2, 3]

    def test_page_capacity_blocks_admission(self):
        cb = ContinuousBatcher(num_slots=4, total_pages=10)
        cb.submit(Request(rid=1, prompt_len=64, max_new_tokens=64))  # 8pg
        cb.submit(Request(rid=2, prompt_len=64, max_new_tokens=64))  # 8pg
        cb.step()
        live = [s.request.rid for s in cb.slots if not s.free]
        assert live == [1]      # r2 waits for pages
        # r1 finishes -> its pages free -> r2 admitted
        for _ in range(70):
            cb.step()
        assert any(r.rid == 2 for r in cb.completed) or \
            any(not s.free and s.request.rid == 2 for s in cb.slots)

    def test_page_accounting_balances(self):
        cb = ContinuousBatcher(num_slots=3, total_pages=50)
        for i in range(6):
            cb.submit(Request(rid=i, prompt_len=16, max_new_tokens=4))
        for _ in range(30):
            cb.step()
        assert cb.free_pages == 50
        assert len(cb.completed) == 6
