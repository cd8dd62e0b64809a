"""Async migration (PR 8): the double-buffered plan/commit split with
one-step-ahead KV prefetch (EXPERIMENTS.md §Async-migration).

Pins:

  * the two-phase commit API (`stage_plan` + `commit_staged`) is
    bitwise identical to `apply_migrations` AND to an independent
    numpy reference executor, over random caches and random plans —
    the split is invisible to every inline call site;
  * the overlap serve pipeline changes WHEN pages move, not what
    attention computes: on an HBM-resident stream (where inline and
    overlap placements coincide) every registered policy emits
    BITWISE the same tokens and terminal statuses as the inline
    engine, on ONE executable per mode; and under real HBM pressure
    the staged pipeline still commits migrations;
  * `revalidate_plan` masks exactly the rows whose sources or
    destinations the interim step invalidated, and keeps index-paired
    swap rows paired;
  * `mask_plan_lanes` drops every row of a stale (rebound) lane;
  * `throttle_plan` over the staged buffer never commits more rows
    than the fault cap — including cap 0, the fallback-to-static
    mode, where plans keep staging and nothing lands.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import configs
from repro.core.tiers import GH200
from repro.kvcache.migrate import (
    MigrationPlan, apply_migrations, commit_staged, stage_plan,
)
from repro.kvcache.paged import CacheGeometry, init_cache
from repro.models.model import Model
from repro.serving import control, trace_bridge
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.faults import FaultPlane, MigrationFault, throttle_plan
from repro.serving.policies import policy_names
from repro.serving.scheduler import Request


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get_smoke("internlm2-1.8b")
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


def _geo():
    return CacheGeometry(num_layers=2, batch=2, page_tokens=4,
                         hbm_pages=4, host_pages=6, kv_heads=2,
                         head_dim=8, dtype=jnp.float32)


def _rand_cache(geo, seed):
    """A cache with every pool/map filled with recognizable noise."""
    rng = np.random.default_rng(seed)
    cache = init_cache(geo)

    def noise(x):
        return jnp.asarray(
            rng.standard_normal(x.shape).astype(np.float32)).astype(x.dtype)

    def owners(x, pages):
        del pages
        return jnp.asarray(
            rng.integers(-1, geo.max_pages, x.shape).astype(np.int32))

    return dataclasses.replace(
        cache,
        k_hbm=noise(cache.k_hbm), v_hbm=noise(cache.v_hbm),
        k_host=noise(cache.k_host), v_host=noise(cache.v_host),
        hbm_owner=owners(cache.hbm_owner, geo.hbm_pages),
        host_owner=owners(cache.host_owner, geo.host_pages),
        page_table=jnp.asarray(rng.integers(
            -1, geo.max_pages, cache.page_table.shape).astype(np.int32)))


def _rand_plan(geo, cap, seed):
    """A random plan with collision-free scatters: at most one row per
    (layer, batch) coordinate, sentinel rows interleaved, ~70% of live
    rows full swaps (demote paired at the same index)."""
    rng = np.random.default_rng(seed)
    arrs = np.full((10, cap), -1, np.int32)
    coords = [(l, b) for l in range(geo.num_layers)
              for b in range(geo.batch)]
    rng.shuffle(coords)
    rows = rng.permutation(cap)[:min(len(coords), cap)]
    for i, (l, b) in zip(rows, coords):
        pro_log = int(rng.integers(0, geo.max_pages))
        arrs[0:5, i] = (l, b, int(rng.integers(0, geo.host_pages)),
                        int(rng.integers(0, geo.hbm_pages)), pro_log)
        if rng.random() < 0.7:
            dem_log = (pro_log + 1) % geo.max_pages
            arrs[5:10, i] = (l, b, arrs[3, i], arrs[2, i], dem_log)
    return MigrationPlan(*[jnp.asarray(a) for a in arrs])


def _ref_apply(cache, plan):
    """Independent numpy executor: gather-everything-first, then
    scatter; owner clears before sets; -1 rows are no-ops."""
    c = jax.tree.map(np.array, cache)
    p = jax.tree.map(np.array, plan)
    hbm_pages = c.k_hbm.shape[2]
    M = p.pro_layer.shape[0]
    staged = []
    for i in range(M):
        dem = pro = None
        if p.dem_layer[i] >= 0:
            l, b, s = p.dem_layer[i], p.dem_batch[i], p.dem_src[i]
            dem = (c.k_hbm[l, b, s].copy(), c.v_hbm[l, b, s].copy())
        if p.pro_layer[i] >= 0:
            l, b, s = p.pro_layer[i], p.pro_batch[i], p.pro_src[i]
            pro = (c.k_host[l, b, s].copy(), c.v_host[l, b, s].copy())
        staged.append((dem, pro))
    for i, (dem, pro) in enumerate(staged):
        if dem is not None:
            l, b = p.dem_layer[i], p.dem_batch[i]
            c.k_host[l, b, p.dem_dst[i]] = dem[0]
            c.v_host[l, b, p.dem_dst[i]] = dem[1]
        if pro is not None:
            l, b = p.pro_layer[i], p.pro_batch[i]
            c.k_hbm[l, b, p.pro_dst[i]] = pro[0]
            c.v_hbm[l, b, p.pro_dst[i]] = pro[1]
    for i in range(M):                      # clears land FIRST
        if p.dem_layer[i] >= 0:
            c.hbm_owner[p.dem_layer[i], p.dem_batch[i], p.dem_src[i]] = -1
    for i in range(M):
        if p.pro_layer[i] >= 0:
            c.hbm_owner[p.pro_layer[i], p.pro_batch[i],
                        p.pro_dst[i]] = p.pro_logical[i]
    for i in range(M):
        if p.pro_layer[i] >= 0:
            c.host_owner[p.pro_layer[i], p.pro_batch[i],
                         p.pro_src[i]] = -1
    for i in range(M):
        if p.dem_layer[i] >= 0:
            c.host_owner[p.dem_layer[i], p.dem_batch[i],
                         p.dem_dst[i]] = p.dem_logical[i]
    for i in range(M):
        if p.dem_layer[i] >= 0:
            c.page_table[p.dem_layer[i], p.dem_batch[i],
                         p.dem_logical[i]] = p.dem_dst[i] + hbm_pages
    for i in range(M):
        if p.pro_layer[i] >= 0:
            c.page_table[p.pro_layer[i], p.pro_batch[i],
                         p.pro_logical[i]] = p.pro_dst[i]
    return c


def _assert_caches_equal(a, b):
    for name in ("k_hbm", "v_hbm", "k_host", "v_host", "page_table",
                 "hbm_owner", "host_owner", "length", "importance"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


# --------------------------------------------------------------------------- #
# the two-phase commit API (unit level)
# --------------------------------------------------------------------------- #

class TestTwoPhaseCommit:
    def _check(self, seed):
        geo = _geo()
        cache = _rand_cache(geo, seed)
        plan = _rand_plan(geo, cap=6, seed=seed + 1)
        out = apply_migrations(cache, plan)
        # split API == fused API, bitwise
        split = commit_staged(cache, plan, stage_plan(cache, plan))
        _assert_caches_equal(out, split)
        # both == the independent numpy reference
        _assert_caches_equal(out, _ref_apply(cache, plan))

    def test_matches_reference_over_seeds(self):
        """Deterministic seed sweep (keeps coverage alive without
        hypothesis): two-phase == apply_migrations == numpy reference
        over random caches and random (sentinel-interleaved) plans."""
        for seed in range(8):
            self._check(seed)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1))
    def test_matches_reference_property(self, seed):
        """Hypothesis-optional widening of the same property."""
        self._check(seed)

    def test_empty_plan_is_identity_with_distinct_buffers(self):
        geo = _geo()
        cache = _rand_cache(geo, 0)
        empty = MigrationPlan.empty(6)
        _assert_caches_equal(cache, apply_migrations(cache, empty))
        # the overlap serve loop DONATES the empty plan as the initial
        # scan carry: ten aliases of one buffer would be rejected by
        # XLA ("attempt to donate the same buffer twice")
        leaves = jax.tree.leaves(empty)
        assert len(leaves) == 10
        ptrs = {x.unsafe_buffer_pointer() for x in leaves}
        assert len(ptrs) == 10

    def test_swap_reads_prepromotion_page(self):
        """The hazard staging exists for: dem_dst == pro_src. The
        demoted page must land in the host slot the promotion vacated
        WITHOUT clobbering the promoted page's trip to HBM."""
        geo = _geo()
        cache = _rand_cache(geo, 3)
        plan = MigrationPlan.build(4, [(0, 0, 2, 1, 5)],
                                   [(0, 0, 1, 2, 6)])
        before_host = np.asarray(cache.k_host[0, 0, 2]).copy()
        before_hbm = np.asarray(cache.k_hbm[0, 0, 1]).copy()
        out = apply_migrations(cache, plan)
        np.testing.assert_array_equal(
            np.asarray(out.k_hbm[0, 0, 1]), before_host)
        np.testing.assert_array_equal(
            np.asarray(out.k_host[0, 0, 2]), before_hbm)
        assert int(out.hbm_owner[0, 0, 1]) == 5
        assert int(out.host_owner[0, 0, 2]) == 6


# --------------------------------------------------------------------------- #
# hazard masking (revalidate_plan / mask_plan_lanes / throttle)
# --------------------------------------------------------------------------- #

class TestHazardMasking:
    def _cache_with_owners(self, geo, ho, eo):
        cache = init_cache(geo)
        return dataclasses.replace(cache, hbm_owner=jnp.asarray(ho),
                                   host_owner=jnp.asarray(eo))

    def test_revalidate_masks_exactly_the_hazards(self):
        geo = _geo()
        L, B = geo.num_layers, geo.batch
        ho = np.full((L, B, geo.hbm_pages), -1, np.int32)
        eo = np.full((L, B, geo.host_pages), -1, np.int32)
        # row 0: valid swap — source still owns logical 5, victim still
        # owns logical 7
        eo[0, 0, 2] = 5
        ho[0, 0, 1] = 7
        # row 1: valid promote-only — source owns 4, dst slot free
        eo[1, 1, 3] = 4
        # row 2: STALE SOURCE — the interim step moved logical 8 away
        eo[0, 1, 1] = 9
        # row 3: promote-only whose dst the interim step OCCUPIED
        eo[1, 0, 0] = 2
        ho[1, 0, 2] = 6
        cache = self._cache_with_owners(geo, ho, eo)
        plan = MigrationPlan(
            pro_layer=jnp.asarray([0, 1, 0, 1, -1], jnp.int32),
            pro_batch=jnp.asarray([0, 1, 1, 0, -1], jnp.int32),
            pro_src=jnp.asarray([2, 3, 1, 0, -1], jnp.int32),
            pro_dst=jnp.asarray([1, 0, 3, 2, -1], jnp.int32),
            pro_logical=jnp.asarray([5, 4, 8, 2, -1], jnp.int32),
            dem_layer=jnp.asarray([0, -1, 0, -1, -1], jnp.int32),
            dem_batch=jnp.asarray([0, -1, 1, -1, -1], jnp.int32),
            dem_src=jnp.asarray([1, -1, 3, -1, -1], jnp.int32),
            dem_dst=jnp.asarray([2, -1, 1, -1, -1], jnp.int32),
            dem_logical=jnp.asarray([7, -1, 3, -1, -1], jnp.int32))
        rv = control.revalidate_plan(plan, cache)
        np.testing.assert_array_equal(
            np.asarray(rv.pro_layer >= 0), [True, True, False, False,
                                            False])
        # demote rows masked with the SAME keep mask (paired swaps)
        np.testing.assert_array_equal(
            np.asarray(rv.dem_layer >= 0), [True, False, False, False,
                                            False])
        # surviving rows are untouched
        assert int(rv.pro_src[0]) == 2 and int(rv.dem_dst[0]) == 2
        assert int(rv.pro_dst[1]) == 0

    def test_revalidate_masks_swap_whose_victim_moved(self):
        """A swap row whose DEMOTE side went stale (the victim slot no
        longer holds the expected logical) must drop whole — promoting
        onto it would clobber an unknown tenant."""
        geo = _geo()
        ho = np.full((geo.num_layers, geo.batch, geo.hbm_pages), -1,
                     np.int32)
        eo = np.full((geo.num_layers, geo.batch, geo.host_pages), -1,
                     np.int32)
        eo[0, 0, 2] = 5          # source fine
        ho[0, 0, 1] = 3          # victim changed: plan expects 7
        cache = self._cache_with_owners(geo, ho, eo)
        plan = MigrationPlan.build(4, [(0, 0, 2, 1, 5)],
                                   [(0, 0, 1, 2, 7)])
        rv = control.revalidate_plan(plan, cache)
        assert not (np.asarray(rv.pro_layer) >= 0).any()
        assert not (np.asarray(rv.dem_layer) >= 0).any()

    def test_mask_plan_lanes_drops_stale_lane_rows(self):
        geo = _geo()
        plan = MigrationPlan(
            pro_layer=jnp.asarray([0, 0, 1, -1], jnp.int32),
            pro_batch=jnp.asarray([0, 1, 1, -1], jnp.int32),
            pro_src=jnp.asarray([1, 2, 3, -1], jnp.int32),
            pro_dst=jnp.asarray([0, 1, 2, -1], jnp.int32),
            pro_logical=jnp.asarray([4, 5, 6, -1], jnp.int32),
            dem_layer=jnp.asarray([0, 0, -1, -1], jnp.int32),
            dem_batch=jnp.asarray([0, 1, -1, -1], jnp.int32),
            dem_src=jnp.asarray([0, 1, -1, -1], jnp.int32),
            dem_dst=jnp.asarray([1, 2, -1, -1], jnp.int32),
            dem_logical=jnp.asarray([7, 8, -1, -1], jnp.int32))
        stale = jnp.asarray([False, True], bool)
        masked = control.mask_plan_lanes(plan, stale)
        np.testing.assert_array_equal(
            np.asarray(masked.pro_layer >= 0),
            [True, False, False, False])
        np.testing.assert_array_equal(
            np.asarray(masked.dem_layer >= 0),
            [True, False, False, False])
        del geo

    def test_throttle_after_revalidate_respects_cap(self):
        """The overlap commit order is revalidate -> throttle: for any
        cap the committed row count never exceeds it, and cap 0 (the
        static-fallback data value) commits nothing."""
        geo = _geo()
        cache = _rand_cache(geo, 11)
        staged = _rand_plan(geo, cap=6, seed=12)
        rv = control.revalidate_plan(staged, cache)
        live = int(np.asarray(rv.pro_layer >= 0).sum())
        for cap in (0, 1, 2, 100):
            t = throttle_plan(rv, jnp.int32(cap))
            n_pro, n_dem = t.row_counts()
            assert int(n_pro) <= cap
            assert int(n_pro) == min(cap, live)
            assert int(n_dem) <= int(n_pro)


# --------------------------------------------------------------------------- #
# the overlap serve pipeline (stream level)
# --------------------------------------------------------------------------- #

def _serve_cfg(policy, **kw):
    sparsity = 0.5 if policy == "quest" else 0.0
    return EngineConfig(max_context=128, hbm_fraction=0.25,
                        policy=policy, attention_sparsity=sparsity,
                        spec=GH200, promote_thresh=0.005,
                        telemetry_stride=4, prefill_chunk=16, **kw)


def _stream(model, n=4, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, model.cfg.vocab,
                                        (16 + 8 * (i % 3),)),
                    max_new_tokens=5) for i in range(n)]


class TestOverlapServe:
    @pytest.mark.parametrize("policy", sorted(policy_names()))
    def test_tokens_and_statuses_match_inline(self, dense_model, policy):
        """The staged pipeline shifts WHEN pages move, never what the
        model computes. On this HBM-resident stream (no spill, so both
        modes hold identical placements throughout) that makes tokens
        and terminal statuses bitwise mode-invariant for every
        registered policy, on one executable per mode — the machinery
        pin: carry threading, lane masking, revalidation, and the
        commit itself perturb nothing. (Under real HBM pressure the
        modes' interim placements differ and the per-tier LSE merge
        may associate floating point differently — semantics, pools
        read, and statuses stay equivalent; bitwise equality is pinned
        where placements coincide.)"""
        model, params = dense_model

        def run(overlap):
            eng = ServingEngine(
                model, params,
                _serve_cfg(policy, overlap_migrations=overlap))
            rep = eng.serve(_stream(model), num_slots=2, seed=0)
            assert eng._serve_jit._cache_size() == 1
            return ({r.rid: list(r.output) for r in rep.completed},
                    rep.statuses)

        toks_i, stat_i = run(False)
        toks_o, stat_o = run(True)
        assert toks_o == toks_i
        assert stat_o == stat_i

    def test_pipeline_commits_under_pressure(self, dense_model):
        """Under real HBM pressure the lagged pipeline must actually
        MOVE pages (a pipeline that stages forever and commits nothing
        would pass every bitwise test), stay within one executable,
        and complete every request ok. The sparse read mask keeps the
        plan-ahead oracle active."""
        model, params = dense_model
        rng = np.random.default_rng(5)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab,
                                            (272 + 16 * (i % 2),)),
                        max_new_tokens=8) for i in range(3)]
        cfg = EngineConfig(max_context=512, hbm_fraction=0.25,
                           policy="importance", attention_sparsity=0.5,
                           spec=GH200, promote_thresh=1e-4,
                           telemetry_stride=8, prefill_chunk=16,
                           overlap_migrations=True)
        eng = ServingEngine(model, params, cfg)
        rep = eng.serve(reqs, num_slots=2, seed=0)
        assert all(s == "ok" for s in rep.statuses.values())
        assert sum(s.m_in + s.m_out for s in eng.stats) > 0
        assert eng._serve_jit._cache_size() == 1

    def test_overlap_hit_fraction_matches_inline(self, dense_model):
        """Under HBM pressure with long decodes, one step of staging
        lag must not change WHERE reads land: the stream's HBM hit
        fraction with the pipeline on is within 0.01 of inline, and
        the pipeline moves pages."""
        model, params = dense_model
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, model.cfg.vocab, (272 + 16 * (i % 2),))
                   for i in range(3)]
        frac = {}
        for overlap in (False, True):
            eng = ServingEngine(model, params, EngineConfig(
                max_context=512, hbm_fraction=0.25, policy="importance",
                attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
                telemetry_stride=8, prefill_chunk=16, trace_telemetry=True,
                overlap_migrations=overlap))
            rep = eng.serve([Request(rid=i, prompt=p,
                                     max_new_tokens=48 + 4 * (i % 2))
                             for i, p in enumerate(prompts)],
                            num_slots=2, seed=0)
            assert all(s == "ok" for s in rep.statuses.values())
            assert sum(s.m_in + s.m_out for s in eng.stats) > 0
            frac[overlap] = trace_bridge.score_serve(
                trace_bridge.collect_serve(eng), GH200,
                oracles=())["aggregate"]["live_hit_fraction"]
        assert abs(frac[True] - frac[False]) <= 0.01, frac

    def test_staged_commits_never_exceed_fault_cap(self, dense_model):
        """Chaos contract, overlap half: a partial-commit window caps
        the STAGED buffer's landing rows per step (visible as migrated
        bytes <= cap * page_bytes), and a full-drop window is
        fallback-to-static — plans stage, nothing commits."""
        model, params = dense_model
        cfg = EngineConfig(max_context=512, hbm_fraction=0.25,
                           policy="importance", attention_sparsity=0.5,
                           spec=GH200, promote_thresh=1e-4,
                           telemetry_stride=8, prefill_chunk=16,
                           overlap_migrations=True)
        eng = ServingEngine(model, params, cfg)
        rng = np.random.default_rng(5)

        def reqs():
            return [Request(rid=i,
                            prompt=rng.integers(0, model.cfg.vocab,
                                                (272 + 16 * (i % 2),)),
                            max_new_tokens=8) for i in range(3)]

        plane = FaultPlane(migration=(
            MigrationFault(start=0, stop=10_000, commit_frac=0.1),))
        eng.serve(reqs(), num_slots=2, seed=0, faults=plane)
        cap_rows = control.plan_capacity(eng.geo,
                                         cfg.migration_budget_frac)
        cap = int(np.ceil(0.1 * cap_rows))
        pb = eng.geo.page_bytes()
        assert any(s.m_in + s.m_out > 0 for s in eng.stats)
        for s in eng.stats:
            assert s.m_in <= cap * pb
            assert s.m_out <= cap * pb
        # full drop == static fallback on the staged buffer
        plane0 = FaultPlane(migration=(
            MigrationFault(start=0, stop=10_000, commit_frac=0.0),))
        eng.serve(reqs(), num_slots=2, seed=0, faults=plane0)
        assert sum(s.m_in + s.m_out for s in eng.stats) == 0
        assert eng._serve_jit._cache_size() == 1
