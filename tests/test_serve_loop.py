"""The continuous-batching fused serve loop (ServingEngine.serve):
single-request bitwise parity with `generate`, mixed-length streams
with zero retraces, page reclaim accounting, sampling reproducibility,
starvation bounds, and the quest-mask plumbing for moe/hybrid/encdec.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.tiers import GH200
from repro.models.model import Model
from repro.serving import control
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.sampling import SamplingConfig, make_sampler
from repro.serving.scheduler import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def dense_model():
    cfg = configs.get_smoke("internlm2-1.8b")
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


def _cfg(policy="importance", sparsity=0.0, stride=4, **kw):
    return EngineConfig(max_context=128, hbm_fraction=0.25, policy=policy,
                        attention_sparsity=sparsity, spec=GH200,
                        promote_thresh=0.005, telemetry_stride=stride,
                        **kw)


class TestServeParity:
    """A single full-length greedy request through `serve` must be the
    same program as prefill + fused `generate`: tokens bitwise equal,
    StepStats identical."""

    @pytest.mark.parametrize("policy,sparsity", [
        ("static", 0.0), ("importance", 0.0), ("importance", 0.5)])
    def test_single_request_matches_generate(self, dense_model, policy,
                                             sparsity):
        model, params = dense_model
        rng = np.random.default_rng(0)
        # prompt length a multiple of page_tokens: serve's page-padded
        # admission prefill is then shape-identical to `start`
        prompt = rng.integers(0, model.cfg.vocab, (32,))
        n = 10

        ref = ServingEngine(model, params, _cfg(policy, sparsity))
        logits0 = ref.start(jnp.asarray(prompt[None], jnp.int32))
        tok0 = jnp.argmax(logits0, -1).astype(jnp.int32)
        toks = ref.generate(tok0, n - 1)
        want = [int(tok0[0])] + [int(t) for t in np.asarray(toks)[:, 0]]

        eng = ServingEngine(model, params, _cfg(policy, sparsity))
        done = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=n)],
                         num_slots=1)
        assert done[0].output == want
        assert eng.stats == ref.stats

    def test_ragged_prompt_pads_to_page_boundary(self, dense_model):
        """Off-page prompt lengths serve fine: pads are invisible."""
        model, params = dense_model
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, model.cfg.vocab, (21,))   # 21 % 16 != 0
        eng = ServingEngine(model, params, _cfg())
        done = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=6)],
                         num_slots=1)
        assert len(done[0].output) == 6
        assert all(0 <= t < model.cfg.vocab for t in done[0].output)


class TestServeStream:
    @pytest.mark.parametrize("overlap", [False, True])
    def test_chunk_shapes_are_the_served_call(self, dense_model, overlap):
        """`serve_chunk_shapes`, which ahead-of-time compiles read, has
        the shapes and dtypes of the arguments `serve` passes."""
        model, params = dense_model
        rng = np.random.default_rng(4)
        reqs = [Request(rid=i, prompt=rng.integers(0, model.cfg.vocab,
                                                    (24,)),
                        max_new_tokens=3) for i in range(2)]
        eng = ServingEngine(model, params,
                            _cfg(overlap_migrations=overlap))
        served = []
        build = eng._chunk_args
        eng._chunk_args = lambda *a: served.append(build(*a)) or served[-1]
        eng.serve(reqs, num_slots=2)
        del eng._chunk_args

        def spec(tree):
            return jax.tree.map(lambda x: (x.shape, x.dtype), tree)
        assert spec(eng.serve_chunk_shapes(2)) == spec(served[0])

    def test_mixed_length_stream_zero_retraces(self, dense_model):
        """More requests than slots, mixed prompt/budget lengths: every
        request completes with its exact budget, the fused chunk
        compiles exactly once, and all pages are reclaimed."""
        model, params = dense_model
        rng = np.random.default_rng(2)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab,
                                            (16 + 8 * (i % 3),)),
                        max_new_tokens=4 + 3 * (i % 3))
                for i in range(6)]
        eng = ServingEngine(model, params, _cfg(stride=4))
        done = eng.serve(reqs, num_slots=2, seed=3)
        assert sorted(r.rid for r in done) == list(range(6))
        for r in done:
            assert len(r.output) == r.max_new_tokens
            assert r.generated == r.max_new_tokens
        # zero retraces after warmup: one executable for the serve chunk
        assert eng._serve_jit._cache_size() == 1
        # byte accounting balances: every page reclaimed on completion
        assert eng.batcher.free_pages == eng.batcher.total_pages
        assert int(np.asarray((eng._cache.hbm_owner >= 0).sum())) == 0
        assert int(np.asarray((eng._cache.host_owner >= 0).sum())) == 0

    def test_eos_stops_early_and_reclaims(self, dense_model):
        """An always-hit EOS (greedy argmax probed first) finishes the
        request before its budget and still balances pages."""
        model, params = dense_model
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, model.cfg.vocab, (32,))
        probe = ServingEngine(model, params, _cfg())
        probed = probe.serve(
            [Request(rid=0, prompt=prompt, max_new_tokens=8)], num_slots=1)
        eos = probed[0].output[2]        # the 3rd greedy token

        eng = ServingEngine(model, params, _cfg(eos_id=int(eos)))
        done = eng.serve(
            [Request(rid=0, prompt=prompt, max_new_tokens=8)], num_slots=1)
        out = done[0].output
        assert len(out) <= 8
        assert out[-1] == eos
        assert eng.batcher.free_pages == eng.batcher.total_pages

    def test_starvation_bound_under_fused_loop(self, dense_model):
        """A page-hungry request blocked behind live slots is admitted
        once completions free its pages — it never starves, and the
        whole stream completes through the fused loop."""
        model, params = dense_model
        rng = np.random.default_rng(4)
        big = Request(rid=0, prompt=rng.integers(0, model.cfg.vocab, (48,)),
                      max_new_tokens=8)           # 4 pages of 16
        smalls = [Request(rid=1 + i,
                          prompt=rng.integers(0, model.cfg.vocab, (16,)),
                          max_new_tokens=4)       # 2 pages each
                  for i in range(4)]
        eng = ServingEngine(model, params, _cfg(stride=4))
        # pool of 6 pages: two smalls fill it; big (4 pages) must wait
        done = eng.serve(smalls + [big], num_slots=2, total_pages=6,
                         seed=0, max_skips=1)
        assert sorted(r.rid for r in done) == list(range(5))
        assert big.started_step > 0          # actually waited
        assert len(big.output) == 8
        assert eng.batcher.free_pages == 6

    def test_moe_family_serves_with_quest_mask(self):
        """serve() drives any cache-backed decode state: moe decodes
        through the same masked, batched hot path."""
        cfg = configs.get_smoke("granite-moe-3b-a800m")
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, (16 + 8 * i,)),
                        max_new_tokens=4 + 2 * i) for i in range(3)]
        eng = ServingEngine(m, params, EngineConfig(
            max_context=96, hbm_fraction=0.25, policy="importance",
            attention_sparsity=0.5, spec=GH200, telemetry_stride=4))
        done = eng.serve(reqs, num_slots=2,
                         sampling=SamplingConfig(temperature=0.7), seed=1)
        assert sorted((r.rid, len(r.output)) for r in done) == \
            [(0, 4), (1, 6), (2, 8)]
        assert eng._serve_jit._cache_size() == 1
        assert eng.batcher.free_pages == eng.batcher.total_pages

    def test_recurrent_family_serve_raises(self):
        cfg = configs.get_smoke("xlstm-125m")
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        eng = ServingEngine(m, params, EngineConfig(max_context=64))
        with pytest.raises(NotImplementedError, match="dense/moe"):
            eng.serve([Request(rid=0, prompt=np.arange(8),
                               max_new_tokens=4)])

    def test_instant_completions_drain_queue(self, dense_model):
        """Requests that finish at admission (budget 1) free their slot
        within the same boundary, so a queue of them drains through one
        slot instead of tripping the no-active-lane guard."""
        model, params = dense_model
        rng = np.random.default_rng(8)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab, (16,)),
                        max_new_tokens=1) for i in range(3)]
        eng = ServingEngine(model, params, _cfg())
        done = eng.serve(reqs, num_slots=1)
        assert sorted(r.rid for r in done) == [0, 1, 2]
        assert all(len(r.output) == 1 for r in done)
        assert eng.batcher.free_pages == eng.batcher.total_pages

    def test_request_objects_reusable_across_serves(self, dense_model):
        """Re-submitting the same Request objects starts a fresh run:
        outputs don't accumulate across serve() calls."""
        model, params = dense_model
        rng = np.random.default_rng(9)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, model.cfg.vocab, (16,)),
                        max_new_tokens=4) for i in range(2)]
        eng = ServingEngine(model, params, _cfg())
        first = {r.rid: list(r.output)
                 for r in eng.serve(reqs, num_slots=1)}
        second = {r.rid: list(r.output)
                  for r in eng.serve(reqs, num_slots=1)}
        assert first == second
        assert all(len(v) == 4 for v in second.values())

    def test_zero_budget_request_rejected(self, dense_model):
        """An invalid decode budget is a per-request rejection with a
        typed error, never a batch-wide abort: the valid neighbor in
        the same stream still completes normally."""
        model, params = dense_model
        rng = np.random.default_rng(5)
        eng = ServingEngine(model, params, _cfg())
        bad = Request(rid=0, prompt=np.arange(8), max_new_tokens=0)
        good = Request(rid=1,
                       prompt=rng.integers(0, model.cfg.vocab, (16,)),
                       max_new_tokens=4)
        report = eng.serve([bad, good], num_slots=1)
        assert bad.status == "rejected"
        assert bad.error.code == "zero_budget"
        assert [r.rid for r in report.rejected] == [0]
        assert good.status == "ok" and len(good.output) == 4
        assert report.statuses == {0: "rejected", 1: "ok"}

    def test_infeasible_request_rejected(self, dense_model):
        """A prompt+budget over the cache capacity is rejected at
        submit (typed error), not raised after the stream started."""
        model, params = dense_model
        rng = np.random.default_rng(5)
        # pool padding (pad_to=16) gives max_context=128 a 512-token
        # capacity; exceed THAT, not the nominal context
        bad = Request(rid=0, prompt=rng.integers(0, model.cfg.vocab,
                                                 (32,)),
                      max_new_tokens=600)
        good = Request(rid=1,
                       prompt=rng.integers(0, model.cfg.vocab, (16,)),
                       max_new_tokens=3)
        eng = ServingEngine(model, params, _cfg())
        report = eng.serve([bad, good], num_slots=1)
        assert bad.status == "rejected"
        assert bad.error.code == "infeasible_context"
        assert good.status == "ok" and len(good.output) == 3
        assert len(report.completed) == 1


class TestServeSampling:
    def test_sampled_decode_reproducible(self, dense_model):
        """Fixed seed -> identical streams; different seed -> the PRNG
        actually samples (some request differs from greedy)."""
        model, params = dense_model
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, model.cfg.vocab, (24,))
                   for _ in range(3)]

        def run(seed, sampling):
            eng = ServingEngine(model, params, _cfg(stride=4))
            done = eng.serve(
                [Request(rid=i, prompt=p, max_new_tokens=6)
                 for i, p in enumerate(prompts)],
                num_slots=2, sampling=sampling, seed=seed)
            return {r.rid: list(r.output) for r in done}

        hot = SamplingConfig(temperature=1.5, top_k=64)
        a = run(0, hot)
        b = run(0, hot)
        assert a == b
        greedy = run(0, SamplingConfig())
        assert any(a[i] != greedy[i] for i in a)

    def test_per_slot_keys_isolate_requests(self, dense_model):
        """A request's sampled tokens don't depend on batch company:
        serving it alone or with neighbours gives the same stream
        (per-request keys derived from (seed, rid))."""
        model, params = dense_model
        rng = np.random.default_rng(7)
        target = rng.integers(0, model.cfg.vocab, (32,))
        other = rng.integers(0, model.cfg.vocab, (32,))
        hot = SamplingConfig(temperature=1.0, top_k=32)

        eng1 = ServingEngine(model, params, _cfg(stride=4))
        solo = eng1.serve([Request(rid=5, prompt=target,
                                   max_new_tokens=6)],
                          num_slots=1, sampling=hot, seed=0)
        eng2 = ServingEngine(model, params, _cfg(stride=4))
        both = eng2.serve([Request(rid=5, prompt=target, max_new_tokens=6),
                           Request(rid=9, prompt=other, max_new_tokens=6)],
                          num_slots=2, sampling=hot, seed=0)
        got = {r.rid: r.output for r in both}
        assert got[5] == solo[0].output


class TestSamplerUnits:
    def test_greedy_is_argmax(self):
        logits = jnp.asarray(np.random.default_rng(0)
                             .standard_normal((3, 17)), jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        out = make_sampler(SamplingConfig())(logits, keys)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.argmax(np.asarray(logits), -1))

    def test_top_k_restricts_support(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((2, 50)), jnp.float32)
        sampler = make_sampler(SamplingConfig(temperature=1.0, top_k=5))
        topk = np.argsort(-np.asarray(logits), -1)[:, :5]
        for s in range(20):
            keys = jax.random.split(jax.random.PRNGKey(s), 2)
            toks = np.asarray(sampler(logits, keys))
            for b in range(2):
                assert toks[b] in topk[b]

    def test_top_p_keeps_nucleus_only(self):
        # one dominant token -> tiny nucleus at modest top_p
        logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]], jnp.float32)
        sampler = make_sampler(SamplingConfig(temperature=1.0, top_p=0.9))
        for s in range(10):
            keys = jax.random.split(jax.random.PRNGKey(s), 1)
            assert int(sampler(logits, keys)[0]) == 0

    def test_zero_temperature_needs_no_key_entropy(self):
        logits = jnp.asarray([[1.0, 3.0, 2.0]], jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(0), 1)
        s1 = make_sampler(SamplingConfig(temperature=0.0))
        assert int(s1(logits, keys)[0]) == 1


class TestLaneOps:
    def _cache(self):
        from repro.kvcache.paged import CacheGeometry, prefill_cache
        geo = CacheGeometry(num_layers=1, batch=2, page_tokens=4,
                            hbm_pages=2, host_pages=4, kv_heads=2,
                            head_dim=8, dtype=jnp.float32)
        rng = np.random.default_rng(0)
        kv = jnp.asarray(rng.standard_normal((1, 2, 16, 2, 8)),
                         jnp.float32)
        return geo, prefill_cache(geo, kv, kv, 16)

    def test_release_lanes_frees_pages(self):
        _, cache = self._cache()
        out = control.release_lanes(cache,
                                    jnp.asarray(np.array([True, False])))
        assert int(np.asarray((out.hbm_owner[:, 0] >= 0).sum())) == 0
        assert int(np.asarray((out.host_owner[:, 0] >= 0).sum())) == 0
        assert int(out.length[0]) == 0
        # untouched lane keeps its pages
        assert int(np.asarray((out.hbm_owner[:, 1] >= 0).sum())) == 2
        assert int(out.length[1]) == 16

    def test_lane_merge_all_active_is_identity(self):
        _, cache = self._cache()
        bumped = dataclasses.replace(cache, length=cache.length + 1,
                                     importance=cache.importance + 1.0)
        out = control.lane_merge(cache, bumped,
                                 jnp.asarray(np.array([True, True])))
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(bumped)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_lane_merge_freezes_inactive(self):
        """The merge restores an inactive lane's length and importance,
        and takes pools and tables from the step whole: NO_WRITE already
        kept the inactive lane's there."""
        _, cache = self._cache()
        bumped = dataclasses.replace(cache, length=cache.length + 1,
                                     importance=cache.importance + 1.0,
                                     k_hbm=cache.k_hbm + 1.0)
        out = control.lane_merge(cache, bumped,
                                 jnp.asarray(np.array([False, True])))
        assert int(out.length[0]) == 16 and int(out.length[1]) == 17
        assert float(out.importance[0, 0, 0]) == 0.0
        assert float(out.importance[0, 1, 0]) == 1.0
        assert out.k_hbm is bumped.k_hbm

    def test_choose_write_slot_gives_inactive_lanes_no_write(self):
        from repro.kvcache.paged import no_write_slot
        geo, cache = self._cache()
        slot = control.choose_write_slot(
            cache, jnp.asarray(np.array([False, True])))
        assert no_write_slot(cache) == geo.hbm_pages + geo.host_pages
        np.testing.assert_array_equal(np.asarray(slot[:, 0]),
                                      no_write_slot(cache))
        np.testing.assert_array_equal(
            np.asarray(slot[:, 1]),
            np.asarray(control.choose_write_slot(cache)[:, 1]))


def _full_pool_merge(old, new, active):
    """The reference frozen-lane merge: a select over every leaf."""
    def m1(o, n):
        lanes = active if n.ndim == 1 else active.reshape(
            (1, -1) + (1,) * (n.ndim - 2))
        return jnp.where(lanes, n, o)
    return jax.tree.map(m1, old, new)


def _mixed_lanes(name):
    """A cache whose seven lanes are in every state a serve step meets:
    decoding into an existing HBM page, a fresh HBM page, an existing
    host page and a fresh host page (HBM full); prefilling; empty; and
    finished (pages still bound until the chunk boundary)."""
    cfg = configs.get_smoke(name)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    geo = m.cache_geometry(7, 64, hbm_fraction=0.5, pad_to=1)
    assert (geo.hbm_pages, geo.host_pages) == (2, 3)
    from repro.kvcache.paged import init_cache
    rng = np.random.default_rng(3)
    n = np.array([20, 16, 40, 32, 8, 0, 24], np.int32)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (7, 48)), jnp.int32)
    _, cache = m.prefill_chunk(params, init_cache(geo), prompts,
                               jnp.zeros((7,), jnp.int32), jnp.asarray(n))
    active = jnp.asarray(np.array([1, 1, 1, 1, 0, 0, 0], bool))
    token = jnp.asarray(rng.integers(0, cfg.vocab, (7,)), jnp.int32)
    return m, params, cache, active, token


class TestFrozenLanes:
    """A lane that is not decoding writes nothing in a serve step: its
    write slot is NO_WRITE, and `lane_merge` selects no pool."""

    @staticmethod
    def _step(name):
        m, params, cache, active, token = _mixed_lanes(name)
        _, stepped = m.decode_step(
            params, cache, token,
            write_slot=control.choose_write_slot(cache, active))
        got = control.lane_merge(cache, stepped, active)
        _, ref = m.decode_step(params, cache, token,
                               write_slot=control.choose_write_slot(cache))
        want = _full_pool_merge(cache, ref, active)
        # frozen lanes: pools, tables and owners are bitwise the
        # pre-step cache already before the merge; importance and
        # length after it
        frozen = ~np.asarray(active)

        def kept(c, field):
            np.testing.assert_array_equal(
                np.asarray(getattr(c, field))[:, frozen],
                np.asarray(getattr(cache, field))[:, frozen], field)

        written = ("k_hbm", "v_hbm", "k_host", "v_host", "page_table",
                   "hbm_owner", "host_owner")
        for field in written:
            kept(stepped, field)
        for field in written + ("importance",):
            kept(got, field)
        np.testing.assert_array_equal(np.asarray(got.length),
                                      np.asarray(cache.length) + ~frozen)
        # every decoding lane registered its page where the reference
        # did, and wrote tokens into both tiers
        for field in ("page_table", "hbm_owner", "host_owner", "length"):
            np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                          np.asarray(getattr(want, field)))
        assert not np.array_equal(np.asarray(got.k_hbm),
                                  np.asarray(cache.k_hbm))
        assert not np.array_equal(np.asarray(got.k_host),
                                  np.asarray(cache.k_host))
        return got, want

    def test_mixed_lane_step_matches_full_pool_merge(self):
        got, want = self._step("internlm2-1.8b")
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_moe_mixed_lane_step_freezes_frozen_lanes(self):
        """MoE routes the whole batch as one group, so frozen lanes
        still compete for expert capacity and a decoding lane's K/V may
        differ from the reference's; what a frozen lane keeps may not."""
        self._step("granite-moe-3b-a800m")

    def test_step_selects_no_pool(self, dense_model):
        """The jaxpr of the serve step with a lane mask has no select
        whose result is pool-shaped ([..., T, KH, HD])."""
        model, params = dense_model
        cfg = model.cfg
        eng = ServingEngine(model, params, _cfg())
        rng = np.random.default_rng(0)
        eng.start(jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                              jnp.int32))
        jaxpr = jax.make_jaxpr(eng._step_jit)(
            params, eng.state, eng._pstate, jnp.zeros((2,), jnp.int32),
            jnp.asarray(np.array([True, False])))
        tail = (cfg.kv_page_tokens, cfg.kv_heads, cfg.head_dim)
        assert _pool_select_eqns(jaxpr.jaxpr, tail) == []


def _pool_select_eqns(jaxpr, tail) -> list:
    """`select_n` equations, nested jaxprs included, whose result shape
    ends in `tail`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and any(
                tuple(v.aval.shape[-3:]) == tail for v in eqn.outvars):
            found.append(eqn)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _pool_select_eqns(sub, tail)
    return found


class TestMaskPlumbing:
    """Quest logical_page_mask flows through every cache-backed family."""

    def _drive_masked(self, name, extra_fn=None, steps=2, sparsity=0.6):
        cfg = configs.get_smoke(name)
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        rng = np.random.default_rng(0)
        B, S = 2, 24
        prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
        extra = extra_fn(cfg, B, rng) if extra_fn else None
        geo = m.cache_geometry(B, 96)
        logits, state = m.prefill(params, prompts, geo, extra=extra)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for _ in range(steps):
            cache = state if not isinstance(state, dict) else state["kv"]
            mask = control.quest_page_mask(cache, sparsity)
            logits, state = m.decode_step(params, state, tok,
                                          logical_page_mask=mask)
            assert np.isfinite(np.asarray(logits, np.float32)).all()
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return logits

    def test_moe_masked_decode(self):
        self._drive_masked("granite-moe-3b-a800m")

    def test_hybrid_masked_decode(self):
        self._drive_masked("zamba2-1.2b")

    def test_encdec_masked_decode(self):
        self._drive_masked(
            "whisper-tiny",
            extra_fn=lambda cfg, B, rng: {
                "frame_embeds": jnp.asarray(
                    rng.standard_normal((B, 8, cfg.d_model)), jnp.float32)})

    def test_recurrent_families_refuse_mask(self):
        cfg = configs.get_smoke("xlstm-125m")
        m = Model(cfg)
        params = m.init(jax.random.key(0))
        st = m.init_decode_state(2)
        with pytest.raises(ValueError, match="paged KV cache"):
            m.decode_step(params, st, jnp.array([1, 2]),
                          logical_page_mask=jnp.ones((1, 2, 4), bool))


class TestSchedulerEngineProtocol:
    def test_pages_needed_uses_engine_page_size(self):
        """Regression: pages_needed once hardcoded page size 16; the
        batcher stamps its geometry's page size at submit."""
        cb = ContinuousBatcher(num_slots=1, total_pages=100, page_tokens=4)
        r = Request(rid=0, prompt_len=10, max_new_tokens=6)
        assert r.pages_needed == 1          # default 16-token pages
        cb.submit(r)
        assert r.pages_needed == 4          # ceil(16 / 4)
        cb.admit()
        assert cb.free_pages == 96

    def test_admit_binds_lanes_and_device_view(self):
        cb = ContinuousBatcher(num_slots=3, total_pages=100)
        for i in range(2):
            cb.submit(Request(rid=i, prompt_len=16, max_new_tokens=8))
        admitted = cb.admit()
        assert [r.lane for r in admitted] == [0, 1]
        view = cb.device_view()
        np.testing.assert_array_equal(view.active,
                                      np.array([True, True, False]))
        np.testing.assert_array_equal(view.remaining[:2], np.array([8, 8]))
        assert view.lane_of == {0: 0, 1: 1}
        cb.complete(admitted[0])
        view = cb.device_view()
        assert not view.active[0] and view.rids[0] == -1
        assert cb.free_pages == 100 - admitted[1].pages_needed

    def test_starvation_bound_limits_leapfrogging(self):
        """The starvation bound caps how many blocked requests may be
        passed over per admission round: with two page-hungry requests
        at the head — FEASIBLE (they fit the whole pool) but blocked
        behind a hog's pages — max_skips=1 admits nothing (the fitting
        smalls may not leapfrog further), max_skips=2 admits them.
        (Requests that could NEVER fit are rejected at submit, not
        skipped — see test_oversized_footprint_rejected_at_submit.)"""
        def build(max_skips):
            cb = ContinuousBatcher(num_slots=4, total_pages=10,
                                   max_skips=max_skips)
            hog = Request(rid=9, prompt_len=64, max_new_tokens=32)
            cb.submit(hog)                  # 6 pages -> 4 left free
            assert [r.rid for r in cb.admit()] == [9]
            cb.submit(Request(rid=0, prompt_len=64, max_new_tokens=64))
            cb.submit(Request(rid=1, prompt_len=64, max_new_tokens=64))
            cb.submit(Request(rid=2, prompt_len=16, max_new_tokens=8))
            cb.submit(Request(rid=3, prompt_len=16, max_new_tokens=8))
            return cb

        strict = build(max_skips=1)
        assert [r.rid for r in strict.admit()] == []
        assert [r.rid for r in strict.queue] == [0, 1, 2, 3]  # FIFO kept

        loose = build(max_skips=2)
        assert [r.rid for r in loose.admit()] == [2, 3]
        assert [r.rid for r in loose.queue] == [0, 1]
