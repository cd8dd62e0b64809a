"""Checks of the four-chip path on four host devices, run in a process
of their own by `test_bench_tp4.py` (the device count is fixed before
JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m bench.tests.tp4 float32|bfloat16|cell

`float32` and `bfloat16` serve a small dense model (4 KV heads, one a
chip) on a data=1, model=4 mesh: weights from `bench.weights.make`
placed by `bench.system.weight_shardings`, the cache placed by the
serve rules, a prefill that spills into the second tier and then
decode steps through the paged cache, each jitted for the mesh. Their
logits are compared with the plain reference. `cell` runs
`granite-8b.decode-tp4` through the harness at that size. Each prints
one JSON line.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import harness, system, weights
from bench.reference import dense
from bench.tests import smoke
from repro import configs
from repro.kernels import ops
from repro.launch import shardings as shd
from repro.models.model import Model

CELL = "granite-8b.decode-tp4"
#: smoke widths with KV heads that divide the model axis
SIZES = dict(smoke.SIZES, num_heads=8, kv_heads=4, rope_theta=1e6,
             norm_eps=1e-5)
PROMPT, TOKENS = 50, 70


def _model(dtype: str) -> Model:
    dt = getattr(jnp, dtype)
    base = configs.get_smoke("granite-8b")
    return Model(dataclasses.replace(base, **SIZES, dtype=dt,
                                     param_dtype=dt))


def logits(dtype: str) -> dict:
    """Prefill then decode on the mesh against the reference."""
    mesh = system.make_mesh({"mesh": {"data": 1, "model": 4}})
    model = _model(dtype)
    wsh = system.weight_shardings(model, mesh)
    w = weights.make(SIZES, dtype, seed=17, shardings=wsh)
    tokens = np.random.default_rng(5).integers(
        0, SIZES["vocab"], TOKENS).astype(np.int32)

    # two HBM pages a lane: the prompt spills into the second tier
    geo = model.cache_geometry(1, 256, hbm_fraction=0.125, pad_to=2)
    cache_sh = shd.cache_shardings(geo, mesh)
    pool = shd.pool_pspec(geo, mesh)
    rep = NamedSharding(mesh, P())

    def prefill(w, toks):
        with ops.sharded_pools(mesh, pool):
            return model.prefill(w, toks, geo)

    def step(w, cache, tok):
        with ops.sharded_pools(mesh, pool):
            return model.decode_step(w, cache, tok)

    prefill = jax.jit(prefill, in_shardings=(wsh, rep),
                      out_shardings=(rep, cache_sh))
    step = jax.jit(step, in_shardings=(wsh, cache_sh, rep),
                   out_shardings=(rep, cache_sh))
    with jax.default_matmul_precision("highest"):
        last, cache = prefill(w, jnp.asarray(tokens[None, :PROMPT]))
        got = [np.asarray(last[0], np.float64)]
        for t in tokens[PROMPT:]:
            out, cache = step(w, cache, jnp.asarray([t]))
            got.append(np.asarray(out[0], np.float64))
    got = np.stack(got)

    n = TOKENS - PROMPT + 1
    fwd = dense.make_forward(SIZES, seq_len=128, n_pos=32)
    toks = np.zeros(128, np.int32)
    toks[:TOKENS] = tokens
    pos = np.zeros(32, np.int32)
    pos[:n] = PROMPT - 1 + np.arange(n)
    want = np.asarray(fwd(w, jnp.asarray(toks), jnp.asarray(pos)),
                      np.float64)[:n]
    return {"dtype": dtype, "devices": len(mesh.devices.flat),
            "pool_spec": [str(a) for a in cache.k_hbm.sharding.spec],
            "hbm_tokens": geo.hbm_pages * geo.page_tokens,
            "max_abs": float(np.abs(got - want).max()),
            "spread": float(want.std()), "shape": list(got.shape)}


def cell() -> dict:
    """The four-chip cell through the harness at a small size, and
    whether each serve call made its cache inside a program that
    places it (never whole on one device)."""
    from repro.serving import engine
    made = []
    init = engine.init_cache

    def init_cache(geo):
        cache = init(geo)
        made.append(isinstance(cache.k_hbm, jax.core.Tracer))
        return cache

    engine.init_cache = init_cache
    _, conf, traffic, _ = harness.load_cell(CELL)
    conf = copy.deepcopy(conf)
    conf["sizes"].update(SIZES)
    out = harness.run_cell(
        CELL, 7, 3.0, False, t_process=time.time(), log=lambda m: None,
        config=conf, traffic=smoke.closed_traffic(traffic, n=10),
        peaks=smoke.PEAKS)
    out["cache_made_in_place"] = made
    return out


if __name__ == "__main__":
    what = sys.argv[1]
    out = cell() if what == "cell" else logits(what)
    print(json.dumps(out))
