"""Serving entry point: the fused two-tier engine, optionally sharded
across a device mesh.

Single device (the default) and a meshed run drive the SAME
`ServingEngine.serve` loop — the mesh only changes where the arrays
live (EXPERIMENTS.md §Mesh-sharding). On a CPU-only box, fake the
devices with XLA host devices (the flag must be set before jax
initializes, i.e. in the environment, not in code):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      PYTHONPATH=src python -m repro.launch.serve --smoke \\
      --mesh data=2,model=2 --requests 6 --new-tokens 8

`--parity` runs the stream twice — unmeshed, then on the mesh — and
checks the contract the tests pin: identical tokens and terminal
statuses, tolerance-close hit/bound fractions, zero retraces under the
mesh. Exit status is the check result, so CI can call it directly.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.sa import SAConfig
from repro.core.tiers import SPECS, spec_for_device
from repro.kernels import ops
from repro.launch import shardings as shd
from repro.launch.jax_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models.model import Model
from repro.serving import trace_bridge
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.policies import policy_names
from repro.serving.scheduler import Request


def parse_mesh(spec: str):
    """'data=2,model=2' -> a (data, model) test mesh; '' -> None.

    Raises a SystemExit with the XLA_FLAGS hint when the host has too
    few devices for the requested shape."""
    if not spec:
        return None
    sizes = {"data": 1, "model": 1}
    for part in spec.split(","):
        name, _, val = part.partition("=")
        if name.strip() not in sizes or not val.strip().isdigit():
            raise SystemExit(
                f"--mesh wants 'data=N,model=M', got {spec!r}")
        sizes[name.strip()] = int(val)
    need = sizes["data"] * sizes["model"]
    have = jax.device_count()
    if need > have:
        raise SystemExit(
            f"--mesh {spec} needs {need} devices, found {have}. On a "
            f"CPU host, set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={need} (before jax starts) to fake them.")
    return make_test_mesh(data=sizes["data"], model=sizes["model"])


def build_requests(vocab: int, n: int, prompt_len: int,
                   new_tokens: int, seed: int = 0):
    """A mixed request stream: three page-rounded prompt lengths and
    staggered budgets, so admissions/completions churn lanes."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab,
                                        (prompt_len + 16 * (i % 3),)),
                    max_new_tokens=new_tokens + 2 * (i % 3))
            for i in range(n)]


def run_stream(model, params, args, mesh, *, trace: bool = False):
    """Serve one stream; returns (engine, ServeReport, wall seconds)."""
    cfg = EngineConfig(
        max_context=args.prompt_len + 32 + args.new_tokens + 16,
        hbm_fraction=args.hbm_fraction, policy=args.policy,
        attention_sparsity=args.sparsity, spec=args.spec,
        telemetry_stride=args.stride, prefill_chunk=16,
        trace_telemetry=trace)
    eng = ServingEngine(model, params, cfg, mesh=mesh)
    reqs = build_requests(model.cfg.vocab, args.requests,
                          args.prompt_len, args.new_tokens)
    t0 = time.perf_counter()
    report = eng.serve(reqs, num_slots=args.batch_slots, seed=args.seed)
    return eng, report, time.perf_counter() - t0


def boundary_summary(chunks) -> dict:
    """One summary of a stream's chunks from `ServeReport.chunks`: the
    host's mean milliseconds per chunk in each phase of `serve()`
    (set-up as its total), the wall milliseconds per step from
    dispatch to readback, the shares (in %) of lane-steps that decoded
    or consumed prompt tokens and of steps in which some lane consumed
    prompt tokens, prompt tokens per step, admissions and releases per
    chunk, and the queue's mean and largest depth at dispatch."""
    if not chunks:
        return {}
    n = len(chunks)
    # in the order the phases first ran
    phases = [p for p in dict.fromkeys(p for c in chunks
                                       for p in c.phase_s) if p != "setup"]
    steps = sum(len(c.stamps) for c in chunks)
    busy = sum(int((c.decoding + c.prefilling).sum()) for c in chunks)
    prefill = sum(int((c.prefilling > 0).sum()) for c in chunks)
    depth = [c.queue_depth for c in chunks]
    return {
        "chunks": n,
        "setup_ms": 1e3 * chunks[0].phase_s.get("setup", 0.0),
        "host_ms": {p: 1e3 * sum(c.phase_s.get(p, 0.0) for c in chunks)
                    / n for p in phases},
        "step_ms": 1e3 * sum(c.t_ready - c.t_dispatch for c in chunks)
                   / steps,
        "lane_occupancy": 100.0 * busy / (steps * len(chunks[0].rids)),
        "prefill_step_share": 100.0 * prefill / steps,
        "prompt_tokens_per_step":
            sum(int(c.prompt_tokens.sum()) for c in chunks) / steps,
        "admitted_per_chunk": sum(c.admitted for c in chunks) / n,
        "released_per_chunk": sum(c.released for c in chunks) / n,
        "queue_depth_mean": sum(depth) / n,
        "queue_depth_max": max(depth),
    }


def sharded_decode_step(model, geo, mesh):
    """`model.decode_step`'s logits, jitted for `mesh` with params and
    a `geo` cache in the serve rules' placement and the Pallas kernel
    (where taken) run per shard. Returns the jitted function and its
    (params, cache, token) shardings."""
    shards = (shd.param_shardings(model.logical_axes(),
                                  model.abstract_params(), mesh, "serve"),
              shd.cache_shardings(geo, mesh),
              NamedSharding(mesh, P(shd.batch_axes(mesh, geo.batch))))
    pool = shd.pool_pspec(geo, mesh)

    def step(params, cache, token):
        with ops.sharded_pools(mesh, pool):
            return model.decode_step(params, cache, token)[0]
    return jax.jit(step, in_shardings=shards), shards


def decode_step_logits(model, params, prompts, geo, mesh=None):
    """Logits [B, V] of one greedy decode step after prefilling
    `prompts` [B, S] into a `geo` cache on one device.

    With `mesh`, only the decode step is partitioned
    (`sharded_decode_step`). Comparing the two isolates the
    partitioned step from prefill and from the serve loop."""
    last, cache = jax.jit(model.prefill, static_argnums=(2,))(
        params, prompts, geo)
    token = jnp.argmax(last, -1).astype(jnp.int32)
    if mesh is None:
        return jax.jit(model.decode_step)(params, cache, token)[0]
    step, shards = sharded_decode_step(model, geo, mesh)
    return step(*jax.device_put((params, cache, token), shards))


def check_parity(model, params, args, mesh) -> bool:
    """Single-device vs meshed serve over the same stream.

    Pins: identical tokens + terminal statuses per request (greedy
    argmax absorbs the mesh's float-reduction reassociation), zero
    retraces under the mesh, and aggregate hit/bound fractions within
    tolerance (migration choices may flip on ulp-level importance-EMA
    differences, which moves telemetry without touching tokens)."""
    ref_eng, ref, _ = run_stream(model, params, args, None, trace=True)
    mesh_eng, got, _ = run_stream(model, params, args, mesh, trace=True)

    ok = True
    exes = mesh_eng._serve_jit._cache_size()
    if exes != 1:
        print(f"PARITY FAIL: {exes} serve executables under mesh")
        ok = False
    if ref.statuses != got.statuses:
        print(f"PARITY FAIL: statuses {ref.statuses} != {got.statuses}")
        ok = False
    ref_out = {r.rid: list(r.output) for r in ref}
    got_out = {r.rid: list(r.output) for r in got}
    for rid in sorted(ref_out):
        if ref_out[rid] != got_out.get(rid):
            print(f"PARITY FAIL: request {rid} tokens diverge\n"
                  f"  1-device: {ref_out[rid]}\n"
                  f"  meshed:   {got_out.get(rid)}")
            ok = False
    sa_cfg = SAConfig(max_evaluations=6, iters_per_level=2, seed=0)
    frac = {}
    for tag, eng, rep in (("1dev", ref_eng, ref),
                          ("mesh", mesh_eng, got)):
        score = trace_bridge.score_serve(
            trace_bridge.collect_serve(eng), args.spec, sa_cfg=sa_cfg,
            report=rep)
        agg = score["aggregate"]
        frac[tag] = (agg["live_hit_fraction"],
                     agg.get("bound_fraction", 0.0))
    d_hit = abs(frac["1dev"][0] - frac["mesh"][0])
    d_bound = abs(frac["1dev"][1] - frac["mesh"][1])
    if d_hit > 0.02 or d_bound > 0.05:
        print(f"PARITY FAIL: fractions drift hit={frac['1dev'][0]:.3f}"
              f"/{frac['mesh'][0]:.3f} bound={frac['1dev'][1]:.3f}"
              f"/{frac['mesh'][1]:.3f}")
        ok = False
    if ok:
        print(f"MESH PARITY OK: {len(ref_out)} requests, tokens + "
              f"statuses identical, hit {frac['mesh'][0]:.3f} "
              f"(d={d_hit:.4f}), bound {frac['mesh'][1]:.3f} "
              f"(d={d_bound:.4f}), 1 executable")
    return ok


def main(argv=None) -> int:
    """CLI driver; returns a process exit status."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--policy", default="importance",
                    choices=list(policy_names()))
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--hbm-fraction", type=float, default=0.25)
    ap.add_argument("--spec", default=None, choices=list(SPECS),
                    help="modeled memory system (default: the one "
                         "the device kind maps to, core.tiers."
                         "SPEC_BY_DEVICE_KIND)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--stride", type=int, default=8,
                    help="fused steps per chunk boundary")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="'data=N,model=M' — serve across a device "
                         "mesh ('' = single device)")
    ap.add_argument("--parity", action="store_true",
                    help="run 1-device AND meshed (default "
                         "data=2,model=2), check tokens/statuses/"
                         "fractions match; exit 1 on divergence")
    args = ap.parse_args(argv)
    enable_compile_cache()
    args.spec = (SPECS[args.spec] if args.spec
                 else spec_for_device(jax.devices()[0]))

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    model = Model(cfg)
    params = model.init(jax.random.key(0))

    if args.parity:
        mesh = parse_mesh(args.mesh or "data=2,model=2")
        return 0 if check_parity(model, params, args, mesh) else 1

    mesh = parse_mesh(args.mesh)
    eng, report, wall = run_stream(model, params, args, mesh)
    total = sum(len(r.output) for r in report)
    s = eng.summary()
    where = (f"mesh {dict(mesh.shape)}" if mesh is not None
             else "1 device")
    print(f"served {len(report)} requests / {total} tokens on {where} "
          f"in {wall:.2f}s ({total / wall:.1f} tok/s wall)")
    if report.ttft:
        print(f"ttft p50 {report.ttft['p50'] * 1e3:.1f} ms  "
              f"tpot p50 {report.tpot.get('p50', 0.0) * 1e3:.2f} ms")
    b = boundary_summary(report.chunks)
    if b:
        phases = ", ".join(f"{p} {ms:.2f}" for p, ms in b["host_ms"].items())
        print(f"host ms per chunk over {b['chunks']} chunks: {phases} "
              f"(setup {b['setup_ms']:.1f} once)")
        print(f"step {b['step_ms']:.2f} ms  lane occupancy "
              f"{b['lane_occupancy']:.1f}%  prefill step share "
              f"{b['prefill_step_share']:.1f}%  prompt tokens/step "
              f"{b['prompt_tokens_per_step']:.1f}  per chunk: admitted "
              f"{b['admitted_per_chunk']:.2f}, released "
              f"{b['released_per_chunk']:.2f}, queue depth "
              f"{b['queue_depth_mean']:.2f} (max {b['queue_depth_max']})")
    print(f"modeled tokens/s {s.get('modeled_tokens_per_s', 0.0):.0f}  "
          f"hbm hit rate {s.get('mean_hbm_hit_rate', 0.0):.2f}  "
          f"kernel slot share {s['kernel_slot_share']:.3f}  "
          f"prefill page share {s['prefill_page_share']:.3f}  "
          f"serve executables {eng._serve_jit._cache_size()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
