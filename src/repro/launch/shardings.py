"""Logical-axis -> mesh-axis sharding rules (divisibility-aware).

One rules engine covers every architecture. Per parameter, each mesh
axis claims at most one tensor dim, chosen by a priority list over the
logical axis names, skipping dims whose size is not divisible by the
mesh axis (GSPMD supports uneven shardings via padding, but divisible
placements avoid the padding waste — the non-divisible cases, e.g.
llama4's 40 heads or granite-moe's 40 experts on a 16-way model axis,
fall through to the next-priority dim and are called out in
EXPERIMENTS.md §Roofline as hillclimb candidates).

Modes:
  train — TP over `model` + FSDP over `data` (embed dim), batch over
          (`pod`, `data`);
  serve — TP over `model`, params replicated over `data`/`pod`, batch
          over `data` (and `pod` when multi-pod).

The serve loop's mesh surface lives here too: `cache_shardings` (the
two-tier paged pools, one layer's layout in `pool_pspec`),
`policy_state_shardings` (per-lane policy state
threaded through the serve scan), and `serve_shardings` (the bundle of
per-lane / per-step specs `ServingEngine` pins on its fused serve
chunk). All rules read only `mesh.axis_names` + `mesh.shape`, so they
work with an `AbstractMesh` (and are unit-testable without devices —
tests/test_shardings.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# priority of logical names for the model (TP/EP) axis
_MODEL_PRIORITY = ("experts", "heads", "kv_heads", "mlp", "vocab",
                   "head_dim", "embed")
# priority for the data (FSDP) axis — train mode only
_FSDP_PRIORITY = ("embed", "vocab", "mlp")


def _axis_sizes(mesh) -> Dict[str, int]:
    """{axis name: size} from any Mesh-like (`Mesh`, `AbstractMesh`,
    or a test stub exposing `.shape` as a name->size mapping)."""
    return dict(mesh.shape)


def _pick_dim(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
              priority, mesh_size: int, taken: set) -> Optional[int]:
    for name in priority:
        for dim, ax in enumerate(axes):
            if ax == name and dim not in taken and \
                    shape[dim] % mesh_size == 0 and shape[dim] >= mesh_size:
                return dim
    return None


def param_pspec(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
                mesh: Mesh, mode: str = "train") -> P:
    """PartitionSpec for one parameter from its logical axis names.

    The `model` axis claims the highest-priority divisible dim
    (`_MODEL_PRIORITY`); in train mode `data` then claims an FSDP dim
    from the remainder. Serve mode replicates over `data`/`pod`."""
    sizes = _axis_sizes(mesh)
    spec = [None] * len(shape)
    taken: set = set()
    if "model" in sizes and sizes["model"] > 1:
        d = _pick_dim(axes, shape, _MODEL_PRIORITY, sizes["model"], taken)
        if d is not None:
            spec[d] = "model"
            taken.add(d)
    if mode == "train" and "data" in sizes and sizes["data"] > 1:
        d = _pick_dim(axes, shape, _FSDP_PRIORITY, sizes["data"], taken)
        if d is not None:
            spec[d] = "data"
            taken.add(d)
    return P(*spec)


def param_shardings(schema_axes: Any, abstract: Any, mesh: Mesh,
                    mode: str = "train") -> Any:
    """Map trees of (logical axes, ShapeDtypeStruct) -> NamedSharding."""
    def one(axes, leaf):
        return NamedSharding(mesh, param_pspec(axes, leaf.shape, mesh, mode))
    return jax.tree.map(one, schema_axes, abstract,
                        is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Activation / batch / state shardings
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh, batch: Optional[int] = None) -> Tuple[str, ...]:
    """Batch mesh axes: the WIDEST suffix of (`pod`, `data`) whose size
    product divides `batch`.

    Degrades axis by axis rather than all-or-nothing: a batch that
    divides the `data` axis but not `pod`×`data` still shards over
    `data` alone (replicating over `pod`) instead of replicating
    everywhere; only a batch no axis divides (e.g. long_500k's
    global_batch=1) drops to full replication. `batch=None` trusts the
    caller and returns every batch axis."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if batch is None:
        return axes
    sizes = _axis_sizes(mesh)
    for start in range(len(axes) + 1):
        cand = axes[start:]
        total = 1
        for a in cand:
            total *= sizes[a]
        if batch % total == 0 and batch >= total:
            return cand
    return ()


def tokens_sharding(mesh: Mesh, batch: Optional[int] = None
                    ) -> NamedSharding:
    """[B, S] token ids: batch-sharded rows, replicated positions."""
    return NamedSharding(mesh, P(batch_axes(mesh, batch), None))


def logits_sharding(mesh: Mesh, vocab: int,
                    batch: Optional[int] = None) -> NamedSharding:
    """[B, V] logits: batch rows + vocab over `model` when divisible."""
    sizes = _axis_sizes(mesh)
    v = "model" if vocab % sizes.get("model", 1) == 0 else None
    return NamedSharding(mesh, P(batch_axes(mesh, batch), v))


def _kv_shard_axis(geo, mesh: Mesh) -> str:
    """Which pool dim carries the model axis.

    kv_heads when divisible (classic TP);
    otherwise PAGES — the LSE merge over pages is associative, so
    page-sharding is exact sequence-parallel attention and keeps every
    chip busy even when kv_heads < model parallelism (llama4/qwen3-class
    GQA with kv=8 on a 16-way axis). Geometry pads pool sizes to 16.
    """
    sizes = _axis_sizes(mesh)
    m = sizes.get("model", 1)
    if geo.kv_heads % m == 0:
        return "kv_heads"
    if geo.hbm_pages % m == 0 and geo.host_pages % m == 0:
        return "pages"
    return "none"


def pool_pspec(geo, mesh: Mesh) -> P:
    """One layer's KV pool [B, P, T, KH, HD]: lanes over data(/pod),
    the model axis on kv_heads or pages per `_kv_shard_axis`.

    `cache_shardings` stacks it under the layer dim, and the engine's
    meshed serve chunk hands it to `ops.sharded_pools`, so the Pallas
    kernel runs per shard on exactly the layout the pools are placed
    in."""
    b_ax = batch_axes(mesh, getattr(geo, "batch", None))
    ax = _kv_shard_axis(geo, mesh)
    return P(b_ax, "model" if ax == "pages" else None, None,
             "model" if ax == "kv_heads" else None, None)


def cache_shardings(geo, mesh: Mesh) -> Any:
    """Shardings for a PagedKVCache pytree.

    Pools [L, B, P, T, KH, HD]: `pool_pspec` under the layer dim.
    Owner/valid tables follow the pools' lanes and pages dims so
    tier_lists stays fully local.
    """
    from repro.kvcache.paged import PagedKVCache
    spec = pool_pspec(geo, mesh)
    b_ax, pg = spec[0], spec[1]
    pool = NamedSharding(mesh, P(None, *spec))
    owner = NamedSharding(mesh, P(None, b_ax, pg))
    table = NamedSharding(mesh, P(None, b_ax, None))
    vec = NamedSharding(mesh, P(b_ax))
    return PagedKVCache(
        k_hbm=pool, v_hbm=pool, k_host=pool, v_host=pool,
        page_table=table, hbm_owner=owner, host_owner=owner,
        length=vec, importance=table)


def policy_state_shardings(state: Any, geo, mesh: Mesh) -> Any:
    """Shardings for a `DevicePolicy.init_state` pytree.

    Policy state rides the serve scan next to the cache, so its lanes
    must co-shard with the cache's lanes: leaves shaped like the page
    table ([L, B, ...], e.g. recency's last-access stamps) take the
    batch axes on dim 1, per-lane [B] vectors take them on dim 0, and
    everything else (cost_aware's scalar payback bars, `()` for the
    stateless policies) replicates. Leaves may be concrete arrays or
    `ShapeDtypeStruct`s."""
    b_ax = batch_axes(mesh, geo.batch)

    def one(leaf):
        shape = leaf.shape
        if len(shape) >= 2 and shape[0] == geo.num_layers \
                and shape[1] == geo.batch:
            return NamedSharding(
                mesh, P(None, b_ax, *([None] * (len(shape) - 2))))
        if len(shape) == 1 and shape[0] == geo.batch:
            return NamedSharding(mesh, P(b_ax))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, state)


def serve_shardings(geo, mesh: Mesh) -> Dict[str, Any]:
    """The sharding bundle `ServingEngine` pins on its fused serve
    chunk (EXPERIMENTS.md §Mesh-sharding has the full rules table).

      cache      PagedKVCache pytree (`cache_shardings`)
      lane       per-lane [B] carries (token/active/remaining/...)
      lane_kv    per-lane 2-D rows ([B, 2] PRNG keys, [B, S] prompts)
      step_lane  per-(step, lane) [stride, B] fault masks + emissions
      rep        replicated scalars/vectors (prefill credits, commit
                 caps — the fault plane is global, not per-shard)
      plan       the staged MigrationPlan carry (overlap mode): ten
                 small [M] int32 rows, replicated — every shard must
                 see the whole plan because revalidation reads owner
                 maps that may live on other shards' page ranges

    Lane axes come from `batch_axes(mesh, geo.batch)`, so a lane count
    the data axis doesn't divide degrades to replication (values
    unchanged, just no data-parallel speedup)."""
    from repro.kvcache.migrate import MigrationPlan
    b_ax = batch_axes(mesh, geo.batch)
    rep = NamedSharding(mesh, P())
    return {
        "cache": cache_shardings(geo, mesh),
        "lane": NamedSharding(mesh, P(b_ax)),
        "lane_kv": NamedSharding(mesh, P(b_ax, None)),
        "step_lane": NamedSharding(mesh, P(None, b_ax)),
        "rep": rep,
        "plan": MigrationPlan(*([rep] * 10)),
    }


def ssm_state_shardings(state: Any, mesh: Mesh) -> Any:
    """Recurrent states: batch over data; heads over model if divisible."""
    sizes = _axis_sizes(mesh)
    m = sizes.get("model", 1)

    def one(leaf):
        # state leaves are [L, B, ...]; try to shard a trailing dim on
        # model if divisible
        b_ax = batch_axes(mesh, leaf.shape[1] if leaf.ndim > 1 else None)
        spec = [None, b_ax] + [None] * (leaf.ndim - 2)
        for dim in range(2, leaf.ndim):
            if leaf.shape[dim] % m == 0 and leaf.shape[dim] >= m:
                spec[dim] = "model"
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, state)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement (every device holds the whole array)."""
    return NamedSharding(mesh, P())


def state_shardings_for(model, state_abs: Any, mesh: Mesh) -> Any:
    """Shardings matching Model.init_decode_state / prefill output."""
    from repro.kvcache.paged import PagedKVCache
    if isinstance(state_abs, PagedKVCache):
        geo = _geo_of(model, state_abs)
        return cache_shardings(geo, mesh)
    if isinstance(state_abs, dict):
        out = {}
        for k, v in state_abs.items():
            if k == "kv":
                out[k] = cache_shardings(_geo_of(model, v), mesh)
            elif k == "enc":
                out[k] = NamedSharding(
                    mesh, P(batch_axes(mesh, v.shape[0]), None, None))
            else:
                out[k] = ssm_state_shardings(v, mesh)
        return out
    return ssm_state_shardings(state_abs, mesh)


def _geo_of(model, cache_abs):
    """Recover a geometry-like view from an abstract cache."""
    import dataclasses

    @dataclasses.dataclass
    class _G:
        kv_heads: int
        head_dim: int
        hbm_pages: int
        host_pages: int
        batch: int
    L, B, Ph, T, KH, HD = cache_abs.k_hbm.shape
    return _G(kv_heads=KH, head_dim=HD, hbm_pages=Ph,
              host_pages=cache_abs.k_host.shape[2], batch=B)
