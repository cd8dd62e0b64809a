"""Compile-only guards for the chip: the serve path's Pallas kernel and
one fused serve chunk, compiled at internlm2-1.8b's published widths
for a described (not attached) TPU v5e.

Nothing runs, so nothing here says anything about results or times.
What it catches is what interpret mode cannot: Mosaic's block-tiling
rules, scalar-prefetch (SMEM) sizes, and a program that does not fit a
chip's memory. The topology is described inside a module fixture, so
no module of the suite touches the TPU library while it is imported,
and every worker collects the same tests.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention
from repro.models.model import Model
from repro.serving.engine import EngineConfig, ServingEngine

#: published TPU v5e HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def internlm2():
    return configs.get("internlm2-1.8b")


@pytest.mark.parametrize("tier,pages", [("hbm", 64), ("host", 208)])
def test_paged_kernel_compiles_for_v5e(one_chip, internlm2, tier, pages):
    """The two pools of `serve()` at B=8, max_context 4096,
    hbm_fraction 0.25: 64 HBM and 208 host pages per lane."""
    cfg = internlm2
    B, T = 8, cfg.kv_page_tokens

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((B, pages, T, cfg.kv_heads, cfg.head_dim), jnp.bfloat16)
    args = (sds((B, cfg.kv_heads, cfg.q_per_kv, cfg.head_dim),
                jnp.bfloat16), pool, pool,
            sds((B, pages), jnp.int32), sds((B, pages), jnp.int32))
    kernel = functools.partial(paged_attention, interpret=False)
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def serve_chunk(one_chip, internlm2):
    """The fused serve chunk `chip_smoke.py` runs, compiled once."""
    with pytest.MonkeyPatch.context() as mp:
        # the engine asks the default backend (the CPU here) whether to
        # take the kernel: steer it to the chip's branch for this compile
        mp.setattr(ops, "_on_tpu", lambda: True)
        model = Model(internlm2)
        eng = ServingEngine(model, model.abstract_params(), EngineConfig(
            max_context=4096, hbm_fraction=0.25, prefill_chunk=64,
            telemetry_stride=32))
        args = eng.serve_chunk_shapes(8, one_chip)
        return eng._serve_jit.lower(*args).compile()


def test_serve_chunk_compiles_with_kernel_and_fits(serve_chunk):
    """The Pallas kernel of both tiers is in the compiled program, and
    its arguments plus temporaries fit one v5e's HBM."""
    assert serve_chunk.as_text().count("tpu_custom_call") >= 2
    mem = serve_chunk.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used


def _pool_selects(hlo: str, cfg, kv_heads=None) -> list:
    """Selects, fused or not, whose result ends in a KV pool's
    [T, KH, HD] (`kv_heads` a shard's, where the pools are sharded)."""
    kh = kv_heads or cfg.kv_heads
    tail = f",{cfg.kv_page_tokens},{kh},{cfg.head_dim}]"
    found = []
    for line in hlo.splitlines():
        op = re.search(r"\sselect\(", line)
        if op and tail in line[:op.start()]:
            found.append(line.strip())
    return found


def test_serve_chunk_selects_no_pool(serve_chunk, internlm2):
    """Lanes that are not decoding drop their token write (NO_WRITE),
    so no step of the chunk selects between two whole pools."""
    assert not _pool_selects(serve_chunk.as_text(), internlm2)


def _pool_all_gathers(hlo: str, cfg, model: int = 2) -> list:
    """All-gathers whose result ends in a KV pool's [T, heads, HD]
    (all heads or one shard's of a `model`-way axis)."""
    T, KH, HD = cfg.kv_page_tokens, cfg.kv_heads, cfg.head_dim
    tails = {f",{T},{kh},{HD}]" for kh in (KH, KH // model)}
    found = []
    for line in hlo.splitlines():
        op = re.search(r"\sall-gather(?:-start)?\(", line)
        if op and any(t in line[:op.start()] for t in tails):
            found.append(line.strip())
    return found


def test_meshed_serve_chunk_runs_kernel_per_shard(topo, internlm2,
                                                  monkeypatch):
    """The serve chunk on data=2,model=2 over the described 2x2: the
    kernel is in the program (per shard, under shard_map), and no KV
    pool is all-gathered to feed it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = Model(internlm2)
    eng = ServingEngine(model, model.abstract_params(), EngineConfig(
        max_context=4096, hbm_fraction=0.25, prefill_chunk=64,
        telemetry_stride=32), mesh=mesh)
    args = eng.serve_chunk_shapes(8)          # builds `_serve_jit`
    hlo = eng._serve_jit.lower(*args).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2
    assert not _pool_all_gathers(hlo, internlm2)


def _computations(hlo: str) -> dict:
    """{computation name: its instruction lines} of an HLO module."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            name = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line).group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line.strip())
    return comps


def _reach(comps: dict, root: str) -> set:
    """`root` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for m in re.finditer(r"(?:body|calls|branch_computations|"
                                 r"to_apply)=\{?([%\w.\-, ]+)\}?", line):
                todo += [x.strip().lstrip("%") for x in m.group(1).split(",")]
    return seen


def _decode_branch_pool_copies(hlo: str, cfg, kv_heads: int) -> list:
    """Pool-shaped copies in the serve step's decode `lax.cond` (the one
    whose branch runs the kernel): in either branch, or in what they
    call. The carried pools stay in place there, in one layout."""
    comps = _computations(hlo)
    tail = f",{cfg.kv_page_tokens},{kv_heads},{cfg.head_dim}]"
    found, conds = [], 0
    for lines in comps.values():
        for line in lines:
            m = re.search(r"\sconditional\(.*branch_computations=\{([^}]*)\}",
                          line)
            if not m:
                continue
            branches = [b.strip().lstrip("%") for b in m.group(1).split(",")]
            reach = [_reach(comps, b) for b in branches]
            if not any("tpu_custom_call" in line
                       for r in reach for c in r for line in comps[c]):
                continue
            conds += 1
            for c in set().union(*reach):
                for line in comps[c]:
                    op = re.search(r"\scopy(?:-start)?\(", line)
                    if op and tail in line[:op.start()]:
                        found.append(line)
    assert conds, "no decode conditional found"
    return found


@pytest.fixture(scope="module")
def granite_tp4(topo):
    """granite-8b's serve chunk at its cell's geometry
    (`granite-8b.decode-tp4`): 16 lanes, 4096 context, HBM tier 1/8,
    data=1, model=4 over the described 2x2, compiled once."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        cfg = configs.get("granite-8b")
        mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        model = Model(cfg)
        eng = ServingEngine(model, model.abstract_params(), EngineConfig(
            max_context=4096, hbm_fraction=0.125, prefill_chunk=64,
            telemetry_stride=32), mesh=mesh)
        args = eng.serve_chunk_shapes(16)
        return cfg, eng._serve_jit.lower(*args).compile()


def test_granite_tp4_chunk_runs_kernel_per_shard_and_fits(granite_tp4):
    """The kernel is in the program per shard, no KV pool is
    all-gathered, and arguments plus temporaries fit a v5e's HBM a
    chip."""
    cfg, compiled = granite_tp4
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    assert not _pool_all_gathers(hlo, cfg, model=4)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, used


def test_granite_tp4_pools_stay_in_place(granite_tp4):
    """With 2 KV heads a shard, no step selects between whole pools, and
    the decode `lax.cond` copies no pool: the migration moves pages in
    the pools' own layout (`migrate._apply_per_shard`), so the pools
    are not relaid out around it."""
    cfg, compiled = granite_tp4
    hlo = compiled.as_text()
    kh = cfg.kv_heads // 4
    assert not _pool_selects(hlo, cfg, kv_heads=kh)
    assert not _decode_branch_pool_copies(hlo, cfg, kh)
