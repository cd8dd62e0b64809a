"""Sharded-vs-single-device serve parity (EXPERIMENTS.md
§Mesh-sharding).

The pins: the same request stream through a 1-device engine and a
mesh-attached engine yields identical terminal statuses, identical
tokens (in float32, and in bf16 wherever no model axis splits a sum),
tolerance-close hit/bound fractions, ONE serve executable with zero
retraces under the mesh, and genuinely sharded cache buffers. Every pin
runs for the smoke model in bf16 (the dtype users serve) and float32.

In bf16 the model axis's partial sums round at other points than the
1-device matmul, and the random smoke model has exact greedy ties (in
the default stream, request 1's third token: two logits both 2.359375
on one device), so a tie can flip. There the partitioned step is
pinned on its first-step logits instead, within a stated tolerance.

The in-process tests need >= 4 jax devices — the CI mesh leg provides
them with `XLA_FLAGS=--xla_force_host_platform_device_count=8`; on a
default 1-device host they skip, and the subprocess test (which spawns
its own 4-device interpreter, XLA_FLAGS must precede jax init) keeps
the parity contract in tier-1 everywhere.
"""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.sa import SAConfig
from repro.core.tiers import GH200
from repro.models.model import Model
from repro.serving import trace_bridge
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 jax devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
#: first-step logits, mesh vs 1 device, as a share of max |logit|.
#: bf16 keeps 8 significant bits (an ulp is 2^-7..2^-6 of a value) and
#: the model axis rounds partial sums at other points, so the logits
#: may differ by a few ulps; a wrong shard layout moves them by O(1).
#: float32 differs only by the reassociation of the partial sums.
LOGIT_RTOL = {"bfloat16": 1 / 16, "float32": 1e-4}


@pytest.fixture(scope="module", params=list(DTYPES))
def model_params(request):
    dtype = DTYPES[request.param]
    cfg = dataclasses.replace(configs.get_smoke("internlm2-1.8b"),
                              dtype=dtype, param_dtype=dtype)
    model = Model(cfg)
    return model, model.init(jax.random.key(0))


def _requests(vocab, n=5, base=32):
    rng = np.random.default_rng(3)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, (base + 16 * (i % 3),)),
                    max_new_tokens=5 + (i % 2))
            for i in range(n)]


def _serve(model, params, mesh, *, policy="importance", trace=False,
           sparsity=0.0, ctx=160, slots=2, reqs=None, overlap=False):
    eng = ServingEngine(model, params, EngineConfig(
        max_context=ctx, hbm_fraction=0.25, policy=policy,
        attention_sparsity=sparsity, spec=GH200, promote_thresh=1e-4,
        telemetry_stride=8, prefill_chunk=16, trace_telemetry=trace,
        overlap_migrations=overlap),
        mesh=mesh)
    report = eng.serve(reqs if reqs is not None
                       else _requests(model.cfg.vocab),
                       num_slots=slots, seed=0)
    return eng, report


def _mesh(data, model):
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(data=data, model=model)


def _tokens(report):
    return {r.rid: list(r.output) for r in report}


def _assert_same_stream(model, ref, got):
    """Statuses always; tokens exactly in float32 (in bf16 a greedy
    tie may flip, see the module docstring)."""
    assert ref.statuses == got.statuses
    assert {r.rid: len(r.output) for r in ref} == \
        {r.rid: len(r.output) for r in got}
    if model.cfg.dtype == jnp.float32:
        assert _tokens(ref) == _tokens(got)


@needs_mesh
def test_mesh_parity_tokens_statuses_zero_retraces(model_params):
    model, params = model_params
    _, ref = _serve(model, params, None)
    eng, got = _serve(model, params, _mesh(2, 2))
    assert eng._serve_jit._cache_size() == 1, \
        eng._serve_jit._cache_size()
    _assert_same_stream(model, ref, got)


@needs_mesh
def test_mesh_first_step_logits_within_dtype_tolerance(model_params):
    """The partitioned decode step itself: one step over the same
    prefilled cache (prompts spill the HBM tier, so both tiers are
    read) on one device and on data=2,model=2."""
    from repro.launch.serve import decode_step_logits
    model, params = model_params
    geo = model.cache_geometry(2, 512, hbm_fraction=0.25)
    rng = np.random.default_rng(3)
    prompts = jnp.asarray(rng.integers(0, model.cfg.vocab, (2, 288)),
                          jnp.int32)
    assert prompts.shape[1] > geo.hbm_pages * geo.page_tokens
    want, got = (np.asarray(decode_step_logits(model, params, prompts,
                                               geo, mesh), np.float32)
                 for mesh in (None, _mesh(2, 2)))
    rtol = LOGIT_RTOL[jnp.dtype(model.cfg.dtype).name]
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


@needs_mesh
def test_mesh_cache_buffers_actually_sharded(model_params):
    model, params = model_params
    eng, _ = _serve(model, params, _mesh(2, 2))
    kh = eng._cache.k_hbm                  # [L, B, Ph, T, KH, HD]
    shards = kh.addressable_shards
    assert len(shards) == 4
    shape = shards[0].data.shape
    assert shape[1] == kh.shape[1] // 2    # lanes over data
    assert shape[4] == kh.shape[4] // 2    # kv_heads over model
    # per-lane carries follow the lanes; fault caps stay replicated
    assert eng._cache.length.addressable_shards[0].data.shape[0] == \
        eng._cache.length.shape[0] // 2


@needs_mesh
def test_mesh_overlap_pipeline_parity(model_params):
    """The async-migration pipeline under a mesh: the staged
    MigrationPlan carry is replicated (launch/shardings.py "plan"
    entry), the commit is a per-shard local scatter, and the overlap
    serve matches the 1-device overlap serve token-for-token on ONE
    executable — the pipeline never forks the compiled surface."""
    model, params = model_params
    _, ref = _serve(model, params, None, overlap=True)
    eng, got = _serve(model, params, _mesh(2, 2), overlap=True)
    assert eng._serve_jit._cache_size() == 1, \
        eng._serve_jit._cache_size()
    _assert_same_stream(model, ref, got)


@needs_mesh
def test_mesh_data_parallel_stateful_policy_parity(model_params):
    # recency threads [L, B, P] state through the scan: a pure
    # data-parallel mesh shards it over lanes and must not perturb it;
    # no model axis splits a sum, so tokens match in bf16 too
    model, params = model_params
    _, ref = _serve(model, params, None, policy="recency")
    eng, got = _serve(model, params, _mesh(4, 1), policy="recency",
                      slots=4)
    assert eng._serve_jit._cache_size() == 1
    assert ref.statuses == got.statuses
    # slots differ (4 lanes vs 2) so scheduling differs; compare the
    # per-request token streams, which sampling keys make lane-invariant
    assert _tokens(ref) == _tokens(got)


@needs_mesh
def test_mesh_hit_bound_fractions_tolerance_pinned(model_params):
    # a stream that actually spills HBM (272/288-token prompts, ctx
    # 512) so the fractions are non-trivial; mesh float reassociation
    # may flip individual migration choices, hence tolerances
    model, params = model_params
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, model.cfg.vocab, (272 + 16 * (i % 2),))
               for i in range(3)]

    def mk():
        return [Request(rid=i, prompt=p, max_new_tokens=4 + (i % 2))
                for i, p in enumerate(prompts)]

    sa_cfg = SAConfig(max_evaluations=6, iters_per_level=2, seed=0)
    frac = {}
    for tag, mesh in (("1dev", None), ("mesh", _mesh(2, 2))):
        eng, rep = _serve(model, params, mesh, trace=True, ctx=512,
                          sparsity=0.5, reqs=mk())
        agg = trace_bridge.score_serve(
            trace_bridge.collect_serve(eng), GH200, sa_cfg=sa_cfg,
            report=rep)["aggregate"]
        frac[tag] = agg
    assert frac["1dev"]["live_hit_fraction"] < 1.0   # stream spilled
    assert abs(frac["1dev"]["live_hit_fraction"]
               - frac["mesh"]["live_hit_fraction"]) <= 0.02
    assert abs(frac["1dev"]["bound_fraction"]
               - frac["mesh"]["bound_fraction"]) <= 0.05


def test_parity_cli_subprocess():
    """Tier-1 everywhere: spawn a 4-host-device interpreter and run
    `repro.launch.serve --parity` (1-device vs data=2,model=2)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    src = os.path.join(REPO, "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--parity", "--requests", "4", "--new-tokens", "6",
         "--batch-slots", "2", "--stride", "8"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=540)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "MESH PARITY OK" in proc.stdout, proc.stdout


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
