"""Serve a few requests on one TPU at internlm2-1.8b's published widths.

The quickest proof that the serve path runs on the chip. In one process
it builds the model with seeded random weights, compiles the fused
serve chunk (which must contain the Pallas paged-attention kernel),
serves a seeded request stream through `ServingEngine.serve()` (some
prompts outgrow a lane's HBM tier, so both tiers' kernels run), checks
every request and the executable count, and compares one decode step
with the Pallas kernel against the reference attention on the same
prefilled cache. The last line of standard output is one JSON object.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 1 device vs data=2,model=2

`--four-chips` runs only the mesh path and what it is compared with,
on a data=2,model=2 mesh over four chips against one device:

- float32 with full-precision matmuls: the same stream served on both,
  tokens and statuses required to be identical. The model axis sums
  partial products in another order than the 1-device matmul; in bf16,
  or in float32 at the TPU's default one-pass bf16 matmul precision,
  those last-bit differences grow over 24 layers until a greedy
  near-tie of the random weights flips;
- bf16, the dtype users serve (the float32 weights rounded): one
  decode step on the mesh against one device on the same prefilled
  cache, logits within `LOGIT_RTOL`, and the meshed serve of the stream
  with every request ok on one executable that holds the kernel.

With no TPU (for example under JAX_PLATFORMS=cpu) it exits non-zero
and prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.tiers import spec_for_device  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kvcache.paged import abstract_cache  # noqa: E402
from repro.launch.jax_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.serve import decode_step_logits  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving.engine import EngineConfig, ServingEngine  # noqa: E402
from repro.serving.scheduler import Request  # noqa: E402

ARCH = "internlm2-1.8b"
#: bf16 decode logits of two paths that should agree (Pallas vs
#: reference attention, or a data=2,model=2 mesh vs one device), as a
#: share of the largest reference logit. The two paths round to bf16
#: (8 significant bits) at different points of each of the 24 layers,
#: and the random-weight residual stream carries those differences to
#: the logits; a wrong head, page, mask or shard moves logits by O(1)
#: of the largest one. The tight check is the kernel's on real pools.
LOGIT_RTOL = 1 / 8
#: kernel vs float32 reference on real prefilled pools (the interpret
#: mode test tolerances of tests/test_kernels.py)
KERNEL_TOL = {"out": 2e-2, "m": 1e-4, "l_rtol": 1e-3, "lse": 1e-3}


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg) -> None:
    """A failed check ends the run (not an `assert`: `-O` drops those)."""
    if not ok:
        raise RuntimeError(msg)


def make_requests(vocab: int, n: int, lo: int, hi: int, spill: int,
                  n_spill: int, new_lo: int, new_hi: int, seed: int):
    """`n` seeded requests; the first `n_spill` prompts are longer than
    `spill` tokens (a lane's HBM tier), the rest are in [lo, hi)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, n)
    lens[:n_spill] = rng.integers(spill + 64, hi, n_spill)
    return [Request(rid=i, prompt=rng.integers(0, vocab, (int(s),)),
                    max_new_tokens=int(rng.integers(new_lo, new_hi + 1)))
            for i, s in enumerate(lens)]


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def init_params(model: Model, seed: int):
    """Seeded random weights, made on the device by one program (one
    compile instead of one per eager op and leaf)."""
    return jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))


def check_stream(eng: ServingEngine, reqs, report, vocab: int) -> None:
    """Every request ends ok with its full budget of in-vocab tokens
    (the engine's non-finite guard fails a lane whose logits go NaN or
    Inf), and the whole stream ran on one serve executable."""
    bad = {rid: s for rid, s in report.statuses.items() if s != "ok"}
    require(not bad, f"requests not ok: {bad}")
    want = {r.rid: r.max_new_tokens for r in reqs}
    for r in report:
        require(len(r.output) == want[r.rid], (r.rid, len(r.output)))
        require(all(0 <= t < vocab for t in r.output), r.rid)
    exes = eng._serve_jit._cache_size()
    require(exes == 1, f"{exes} serve executables")


def compile_serve_chunk(eng: ServingEngine, num_slots: int):
    """AOT-compile the inline serve chunk for `num_slots` lanes from
    shapes alone; returns (seconds, number of Pallas custom calls)."""
    shapes = eng.serve_chunk_shapes(num_slots)
    t0 = time.perf_counter()
    compiled = eng._serve_jit.lower(*shapes).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    log(f"serve chunk memory_analysis: arguments "
        f"{mem.argument_size_in_bytes} bytes, temporaries "
        f"{mem.temp_size_in_bytes} bytes")
    return seconds, compiled.as_text().count("tpu_custom_call")


def serve_stream(eng: ServingEngine, reqs, num_slots: int, seed: int):
    t0 = time.perf_counter()
    report = eng.serve(reqs, num_slots=num_slots, seed=seed)
    return report, time.perf_counter() - t0


def compare_decode(model: Model, params, *, batch: int, prompt: int,
                   max_context: int, hbm_fraction: float, seed: int):
    """One decode step with the Pallas kernel and with the reference
    attention on the same prefilled cache (prompt longer than a lane's
    HBM tier, so both tiers hold pages). Returns (max |dlogits|,
    max |reference logit|, lanes whose argmax agrees, kernel errors)."""
    geo = model.cache_geometry(batch, max_context,
                               hbm_fraction=hbm_fraction)
    require(prompt > geo.hbm_pages * geo.page_tokens, "prompt must spill")
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab, (batch, prompt)),
                         jnp.int32)
    last, cache = jax.jit(model.prefill, static_argnums=(2,))(
        params, tokens, geo)
    token = jnp.argmax(last, -1).astype(jnp.int32)
    got, want = (np.asarray(jax.jit(functools.partial(
        model.decode_step, use_pallas=p))(params, cache, token)[0],
        np.float32) for p in (True, False))

    # the kernel alone on layer 0's pools, against the float32 oracle
    hl, hv, el, ev = cache.tier_lists(layer=0)
    cfg = model.cfg
    q = jnp.asarray(rng.standard_normal(
        (batch, cfg.kv_heads, cfg.q_per_kv, cfg.head_dim)), cfg.dtype)
    errs = {}
    for tier, (k, v, pl_, pv) in {
            "hbm": (cache.k_hbm[0], cache.v_hbm[0], hl, hv),
            "host": (cache.k_host[0], cache.v_host[0], el, ev)}.items():
        o_k, m_k, l_k, lse_k = jax.jit(functools.partial(
            ops.tier_attention, use_pallas=True))(q, k, v, pl_, pv)
        with jax.default_matmul_precision("highest"):
            o_r, m_r, l_r, lse_r = jax.jit(ref.paged_attention_ref)(
                q, k, v, pl_, pv)
        f = functools.partial(np.asarray, dtype=np.float32)
        errs[tier] = {
            "out": float(np.abs(f(o_k) - f(o_r)).max()),
            "m": float(np.abs(f(m_k) - f(m_r)).max()),
            "l_rel": float((np.abs(f(l_k) - f(l_r))
                            / np.maximum(np.abs(f(l_r)), 1e-6)).max()),
            "lse": float(np.abs(f(lse_k) - f(lse_r)).max()),
        }
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    return (float(np.abs(got - want).max()), float(np.abs(want).max()),
            agree, errs)


def run_one_chip(seed: int) -> None:
    cfg = configs.get(ARCH)
    model = Model(cfg)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    params = init_params(model, seed)
    log(f"weights: {nbytes(params)} bytes ({cfg.name}, "
        f"{time.perf_counter() - t0:.1f} s to init)")

    B = 8
    ecfg = EngineConfig(max_context=4096, hbm_fraction=0.25,
                        spec=spec_for_device(dev), prefill_chunk=64,
                        telemetry_stride=32)
    eng = ServingEngine(model, params, ecfg)
    compile_s, n_kernel = compile_serve_chunk(eng, B)
    geo = eng.geo
    log(f"cache: {nbytes(abstract_cache(geo))} bytes (B={B}, "
        f"{geo.hbm_pages}+{geo.host_pages} pages of {geo.page_tokens} "
        f"tokens per lane and layer)")
    log(f"serve chunk compile: {compile_s:.1f} s, "
        f"tpu_custom_call count {n_kernel}")
    require(n_kernel > 0, "the serve chunk holds no Pallas kernel")

    hbm_tokens = geo.hbm_pages * geo.page_tokens
    reqs = make_requests(cfg.vocab, 10, 256, 2560, hbm_tokens, 4,
                         32, 64, seed)
    report, serve_s = serve_stream(eng, reqs, B, seed)
    check_stream(eng, reqs, report, cfg.vocab)
    tokens = sum(len(r.output) for r in report)
    prompt_tokens = sum(r.prompt_len for r in reqs)
    spilled = sum(r.prompt_len > hbm_tokens for r in reqs)
    log(f"served {len(report)} requests: all ok, {tokens} new tokens, "
        f"{prompt_tokens} prompt tokens ({spilled} prompts over the "
        f"{hbm_tokens}-token HBM tier), {serve_s:.1f} s, "
        f"1 serve executable")
    eng.state = None                       # free the stream's cache

    lanes = 2
    d, scale, agree, errs = compare_decode(
        model, params, batch=lanes, prompt=hbm_tokens + 512,
        max_context=4096, hbm_fraction=0.25, seed=seed)
    log(f"pallas vs reference decode step: max |dlogits| {d:.5f} "
        f"(limit {LOGIT_RTOL * scale:.5f} = {LOGIT_RTOL} x max |logit| "
        f"{scale:.4f}), argmax agrees on {agree}/{lanes} lanes")
    log(f"kernel vs float32 oracle on layer 0 pools: {json.dumps(errs)}")
    require(d <= LOGIT_RTOL * scale, "Pallas and reference logits differ")
    for tier, e in errs.items():
        require(e["out"] <= KERNEL_TOL["out"], (tier, e))
        require(e["m"] <= KERNEL_TOL["m"], (tier, e))
        require(e["l_rel"] <= KERNEL_TOL["l_rtol"], (tier, e))
        require(e["lse"] <= KERNEL_TOL["lse"], (tier, e))
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


def run_four_chips(seed: int) -> None:
    """One device vs a data=2,model=2 mesh over four chips: float32
    token parity, then bf16 (see the module docstring)."""
    require(jax.device_count() >= 4,
            f"--four-chips needs 4 devices, found {jax.device_count()}")
    with jax.default_matmul_precision("highest"):
        params32 = _four_chip_parity(seed)
    model = Model(configs.get(ARCH))
    params = jax.device_put(jax.tree.map(
        lambda x, a: x.astype(a.dtype), params32, model.abstract_params()),
        jax.devices()[0])
    del params32
    _four_chip_bf16(model, params, seed)


def _four_chip_config(model: Model, seed: int):
    """Engine config, lanes and request stream of both legs: float32
    weights and cache fit one chip at B=4 and max_context 1024."""
    B = 4
    ecfg = EngineConfig(max_context=1024, hbm_fraction=0.25,
                        spec=spec_for_device(jax.devices()[0]),
                        prefill_chunk=64, telemetry_stride=32)
    geo = model.cache_geometry(B, ecfg.max_context,
                               hbm_fraction=ecfg.hbm_fraction)
    hbm_tokens = geo.hbm_pages * geo.page_tokens
    reqs = functools.partial(make_requests, model.cfg.vocab, 8, 128, 768,
                             hbm_tokens, 4, 32, 48, seed)
    return B, ecfg, geo, reqs


def _four_chip_parity(seed: int):
    """float32: the same stream on one device and on the mesh, tokens
    and statuses identical. Returns the mesh-placed weights."""
    cfg = dataclasses.replace(configs.get(ARCH), dtype=jnp.float32,
                              param_dtype=jnp.float32)
    model = Model(cfg)
    params = init_params(model, seed)
    log(f"weights: {nbytes(params)} bytes ({cfg.name}, float32)")
    B, ecfg, _, reqs = _four_chip_config(model, seed)

    one = ServingEngine(model, params, ecfg)
    ref_report, ref_s = serve_stream(one, reqs(), B, seed)
    check_stream(one, reqs(), ref_report, cfg.vocab)
    log(f"1 device: {len(ref_report)} requests ok, "
        f"{sum(len(r.output) for r in ref_report)} tokens, {ref_s:.1f} s")
    del one

    mesh = make_test_mesh(data=2, model=2)
    eng = ServingEngine(model, params, ecfg, mesh=mesh)
    del params
    compile_s, n_kernel = compile_serve_chunk(eng, B)
    log(f"mesh {dict(mesh.shape)} serve chunk compile: {compile_s:.1f} s,"
        f" tpu_custom_call count {n_kernel}")
    require(n_kernel > 0, "the meshed serve chunk holds no Pallas kernel")
    report, serve_s = serve_stream(eng, reqs(), B, seed)
    check_stream(eng, reqs(), report, cfg.vocab)
    require(report.statuses == ref_report.statuses, "statuses differ")
    want = {r.rid: list(r.output) for r in ref_report}
    got = {r.rid: list(r.output) for r in report}
    diff = sorted(rid for rid in want if want[rid] != got.get(rid))
    for rid in diff:
        at = next(i for i, (a, b) in enumerate(zip(want[rid], got[rid]))
                  if a != b)
        log(f"request {rid}: first differing token {at} of "
            f"{len(want[rid])}")
    require(not diff, f"requests {diff} diverge between 1 device and mesh")
    log(f"mesh parity: {len(got)} requests, tokens and statuses "
        f"identical, 1 executable, {serve_s:.1f} s")
    return eng.params


def _four_chip_bf16(model: Model, params, seed: int) -> None:
    """bf16: one decode step on the mesh against one device, then the
    meshed serve of the stream."""
    cfg = model.cfg
    B, ecfg, geo, reqs = _four_chip_config(model, seed)
    mesh = make_test_mesh(data=2, model=2)
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab, (B, geo.hbm_pages * geo.page_tokens + 256)),
        jnp.int32)
    want, got = (np.asarray(decode_step_logits(model, params, prompts,
                                               geo, m), np.float32)
                 for m in (None, mesh))
    d, scale = float(np.abs(got - want).max()), float(np.abs(want).max())
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    log(f"bf16 mesh vs 1 device decode step: max |dlogits| {d:.5f} "
        f"(limit {LOGIT_RTOL * scale:.5f} = {LOGIT_RTOL} x max |logit| "
        f"{scale:.4f}), argmax agrees on {agree}/{B} lanes")
    require(d <= LOGIT_RTOL * scale, "bf16 mesh and 1-device logits differ")

    eng = ServingEngine(model, params, ecfg, mesh=mesh)
    compile_s, n_kernel = compile_serve_chunk(eng, B)
    log(f"bf16 mesh {dict(mesh.shape)} serve chunk compile: "
        f"{compile_s:.1f} s, tpu_custom_call count {n_kernel}")
    require(n_kernel > 0, "the meshed serve chunk holds no Pallas kernel")
    report, serve_s = serve_stream(eng, reqs(), B, seed)
    check_stream(eng, reqs(), report, cfg.vocab)
    log(f"bf16 mesh serve: {len(report)} requests ok, "
        f"{sum(len(r.output) for r in report)} tokens, 1 executable, "
        f"{serve_s:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the 1-device vs data=2,model=2 parity")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r});"
              f" this script runs only on a TPU", file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
        f"compile cache {cache} holds {warm} entries at start")
    if args.four_chips:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
