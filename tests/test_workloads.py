"""Workload-plane property suite (benchmarks/workloads.py).

The determinism contract under test: a `WorkloadSpec` (seed included)
IS the stream — two `generate()` calls produce bitwise-identical
arrival times, lengths, tiers, prompt tokens, and sampling keys. Plus
the distributional invariants: arrivals sorted and strictly positive,
lengths >= 1 and page-snapped when asked, tier names from the spec's
mix, and the truncated-Zipf tail sampler within KS tolerance of the
exact law it inverts (the CDF is exposed for exactly this test). And
the serve loop's side of the contract: every arrival process drives
ONE serve executable.

Property tests are hypothesis-optional (tests/_hypothesis_compat);
deterministic smoke companions keep the coverage alive without it.
"""

import os
import sys

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                os.pardir, "benchmarks"))
import workloads as wl                                   # noqa: E402
from repro import configs                                # noqa: E402
from repro.core.tiers import GH200                       # noqa: E402
from repro.models.model import Model                     # noqa: E402
from repro.serving.engine import EngineConfig, ServingEngine  # noqa: E402
from repro.serving.scheduler import TERMINAL_STATUSES    # noqa: E402


def _spec(**kw):
    base = dict(seed=5, n_requests=48, rate_rps=40.0, max_prompt=64,
                max_new=12, vocab=128)
    base.update(kw)
    return wl.WorkloadSpec(**base)


def _assert_bitwise_equal(a: wl.Workload, b: wl.Workload) -> None:
    assert a.arrival_s.tobytes() == b.arrival_s.tobytes()
    assert a.prompt_len.tobytes() == b.prompt_len.tobytes()
    assert a.max_new.tobytes() == b.max_new.tobytes()
    assert a.tier == b.tier
    assert len(a.prompts) == len(b.prompts)
    assert all(x.tobytes() == y.tobytes()
               for x, y in zip(a.prompts, b.prompts))
    assert a.stream_seed == b.stream_seed
    assert a.sampling == b.sampling


# --------------------------------------------------------------------------- #
# determinism: the seed IS the stream
# --------------------------------------------------------------------------- #

class TestDeterminism:
    def test_same_seed_bitwise_identical_every_arrival(self):
        for arrival in wl.ARRIVALS:
            spec = _spec(arrival=arrival, temperature=0.8)
            _assert_bitwise_equal(wl.generate(spec), wl.generate(spec))

    def test_different_seed_different_stream(self):
        a = wl.generate(_spec(seed=1))
        b = wl.generate(_spec(seed=2))
        assert a.arrival_s.tobytes() != b.arrival_s.tobytes()
        assert a.stream_seed != b.stream_seed

    def test_mixed_stream_deterministic(self):
        a = wl.mixed_stream(7, 24, vocab=64)
        b = wl.mixed_stream(7, 24, vocab=64)
        _assert_bitwise_equal(a, b)

    def test_requests_fresh_objects_with_stamps(self):
        """requests() materialises fresh Request objects each call (the
        engine mutates them) carrying the stream's arrival offsets and
        tiers; time_scale stretches the clock, open_loop=False drops
        it."""
        w = wl.generate(_spec(seed=9))
        r1, r2 = w.requests(), w.requests()
        assert [r.rid for r in r1] == [r.rid for r in r2]
        r1[0].output.append(1)
        assert not r2[0].output
        for i, r in enumerate(r1):
            assert r.arrival_s == float(w.arrival_s[i])
            assert r.tier == w.tier[i]
            assert r.prompt_len == int(w.prompt_len[i])
        half = w.requests(time_scale=0.5)
        assert all(abs(h.arrival_s - r.arrival_s * 0.5) < 1e-12
                   for h, r in zip(half, r1))
        closed = w.requests(open_loop=False)
        assert all(r.arrival_s == 0.0 for r in closed)

    def test_sampled_stream_contract(self):
        w = wl.generate(_spec(temperature=0.7, top_k=20))
        kw = w.serve_kwargs()
        assert kw["seed"] == w.stream_seed
        assert kw["sampling"].temperature == 0.7
        assert kw["sampling"].top_k == 20


# --------------------------------------------------------------------------- #
# structural invariants
# --------------------------------------------------------------------------- #

class TestInvariants:
    def test_arrivals_sorted_and_positive(self):
        for arrival in wl.ARRIVALS:
            w = wl.generate(_spec(arrival=arrival))
            assert (np.diff(w.arrival_s) >= 0).all(), arrival
            assert (w.arrival_s > 0).all(), arrival

    def test_lengths_bounded(self):
        w = wl.generate(_spec(seed=13))
        assert (w.prompt_len >= 1).all()
        assert (w.prompt_len <= w.spec.max_prompt).all()
        assert (w.max_new >= 1).all()
        assert (w.max_new <= w.spec.max_new).all()
        assert all(len(p) == n
                   for p, n in zip(w.prompts, w.prompt_len))
        assert all((p >= 0).all() and (p < w.spec.vocab).all()
                   for p in w.prompts)

    def test_snap_frac_one_page_aligns_everything(self):
        w = wl.generate(_spec(seed=3, snap_frac=1.0, page_tokens=16))
        aligned = (w.prompt_len % 16 == 0) | \
            (w.prompt_len == w.spec.max_prompt)
        assert aligned.all(), w.prompt_len

    def test_tiers_from_mix(self):
        w = wl.generate(_spec(seed=21, n_requests=400))
        names = [t for t, _ in w.spec.tiers]
        assert set(w.tier) <= set(names)
        # the dominant tier dominates (loose: no exact-frequency pin)
        counts = {t: w.tier.count(t) for t in names}
        assert counts["interactive"] > counts["batch"]

    def test_merge_sorts_and_preserves_rows(self):
        a = wl.generate(_spec(seed=1, n_requests=10))
        b = wl.generate(_spec(seed=2, n_requests=6, arrival="bursty"))
        m = wl.merge([a, b])
        assert m.n == 16
        assert (np.diff(m.arrival_s) >= 0).all()
        # every (length, prompt) row survives the shuffle
        assert sorted(m.prompt_len) == sorted(
            list(a.prompt_len) + list(b.prompt_len))
        assert all(len(p) == n
                   for p, n in zip(m.prompts, m.prompt_len))

    def test_bursty_is_burstier_than_poisson(self):
        """On-off modulation shows up as higher gap dispersion than the
        exponential stream's at the same mean rate."""
        po = wl.generate(_spec(seed=17, n_requests=600))
        bu = wl.generate(_spec(seed=17, n_requests=600,
                               arrival="bursty"))
        cv = lambda g: np.std(g) / np.mean(g)          # noqa: E731
        assert cv(np.diff(bu.arrival_s)) > cv(np.diff(po.arrival_s))


# --------------------------------------------------------------------------- #
# the Zipf tail sampler vs the exact law it inverts
# --------------------------------------------------------------------------- #

class TestZipf:
    def test_cdf_is_a_cdf(self):
        cdf = wl.zipf_cdf(1.3, 512)
        assert cdf.shape == (512,)
        assert (np.diff(cdf) > 0).all()
        assert abs(cdf[-1] - 1.0) < 1e-12

    def test_ks_within_tolerance(self):
        """Large-n empirical CDF of `sample_zipf` vs the exact
        truncated-Zipf CDF: the KS statistic stays under the 1%
        critical value (the sampler is exact inverse-CDF, so the only
        deviation is sampling noise)."""
        n, support, alpha = 20_000, 256, 1.3
        rng = np.random.default_rng(0)
        draws = wl.sample_zipf(rng, alpha, support, n)
        assert draws.min() >= 1 and draws.max() <= support
        cdf = wl.zipf_cdf(alpha, support)
        emp = np.searchsorted(np.sort(draws),
                              np.arange(1, support + 1),
                              side="right") / n
        ks = np.abs(emp - cdf).max()
        assert ks < 1.63 / np.sqrt(n), ks          # KS alpha=0.01

    def test_heavier_alpha_shortens_tail(self):
        rng = np.random.default_rng(1)
        light = wl.sample_zipf(rng, 1.1, 256, 4000)
        rng = np.random.default_rng(1)
        heavy = wl.sample_zipf(rng, 2.5, 256, 4000)
        assert heavy.mean() < light.mean()


# --------------------------------------------------------------------------- #
# hypothesis-driven generalisations of the above
# --------------------------------------------------------------------------- #

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(wl.ARRIVALS))
def test_property_seed_is_the_stream(seed, arrival):
    """Any (seed, arrival process): generation is bitwise reproducible
    and the structural invariants hold."""
    spec = _spec(seed=seed, n_requests=24, arrival=arrival)
    a, b = wl.generate(spec), wl.generate(spec)
    _assert_bitwise_equal(a, b)
    assert (np.diff(a.arrival_s) >= 0).all()
    assert (a.arrival_s > 0).all()
    assert (a.prompt_len >= 1).all()
    assert (a.prompt_len <= spec.max_prompt).all()
    assert all(len(p) == n for p, n in zip(a.prompts, a.prompt_len))


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.floats(1.05, 3.0, allow_nan=False))
def test_property_zipf_support(seed, alpha):
    rng = np.random.default_rng(seed)
    draws = wl.sample_zipf(rng, alpha, 128, 500)
    assert draws.min() >= 1 and draws.max() <= 128


# --------------------------------------------------------------------------- #
# arrival processes are data: one serve executable for all of them
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def dense_model():
    m = Model(configs.get_smoke("internlm2-1.8b"))
    return m, m.init(jax.random.key(0))


@pytest.mark.parametrize("arrival", wl.ARRIVALS)
def test_arrival_process_one_serve_executable(dense_model, arrival):
    """A closed-loop stream and then an open-loop stream of `arrival`,
    sampled and stopping on EOS, through ONE engine: every request ends
    in a terminal status and the serve chunk compiles once. The arrival
    clock is compressed so the open-loop stream waits well under a
    second."""
    model, params = dense_model
    eng = ServingEngine(model, params, EngineConfig(
        max_context=128, hbm_fraction=0.25, policy="importance",
        attention_sparsity=0.5, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=4, prefill_chunk=16, prefill_budget=24,
        eos_id=model.cfg.eos_id))
    base = dict(n_requests=3, rate_rps=8.0, min_prompt=16, max_prompt=64,
                max_new=6, vocab=model.cfg.vocab, temperature=0.7)
    closed = wl.generate(wl.WorkloadSpec(seed=3, **base))
    stream = wl.generate(wl.WorkloadSpec(seed=11, arrival=arrival, **base))
    scale = min(1.0, 0.25 / float(stream.arrival_s[-1]))
    for reqs, wload in ((closed.requests(open_loop=False), closed),
                        (stream.requests(time_scale=scale), stream)):
        rep = eng.serve(reqs, num_slots=2, **wload.serve_kwargs())
        assert set(rep.statuses) == {r.rid for r in reqs}
        assert all(s in TERMINAL_STATUSES for s in rep.statuses.values())
    assert eng._serve_jit._cache_size() == 1
