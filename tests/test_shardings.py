"""Unit tests for the mesh sharding rules (repro.launch.shardings).

The rules read only `mesh.axis_names` + `mesh.shape`, so an
`AbstractMesh` (axis names + sizes, no devices) exercises every
divisibility branch on the 1-device tier-1 CI legs — including mesh
shapes (16x16, pods) far bigger than any test host.
"""

from types import SimpleNamespace

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch import shardings as shd


def am(**sizes):
    return AbstractMesh(tuple(sizes.values()), tuple(sizes))


def geo_stub(*, kv_heads=2, hbm_pages=16, host_pages=16, batch=4,
             num_layers=2, max_pages=8):
    return SimpleNamespace(kv_heads=kv_heads, head_dim=16,
                           hbm_pages=hbm_pages, host_pages=host_pages,
                           batch=batch, num_layers=num_layers,
                           max_pages=max_pages)


# --------------------------------------------------------------------- #
# batch_axes: the widest-divisible-suffix rule (ISSUE 7 satellite fix)
# --------------------------------------------------------------------- #

def test_batch_axes_full_divisibility_uses_every_axis():
    assert shd.batch_axes(am(pod=2, data=4, model=2), 16) == \
        ("pod", "data")


def test_batch_axes_falls_back_to_data_not_replication():
    # batch 4 divides data=4 but not pod*data=8: the pre-fix code
    # replicated everywhere; now it shards over data alone
    assert shd.batch_axes(am(pod=2, data=4, model=2), 4) == ("data",)


def test_batch_axes_indivisible_batch_replicates():
    assert shd.batch_axes(am(pod=2, data=4, model=2), 3) == ()
    assert shd.batch_axes(am(data=2, model=2), 1) == ()


def test_batch_axes_none_trusts_caller():
    assert shd.batch_axes(am(pod=2, data=4, model=2)) == ("pod", "data")
    assert shd.batch_axes(am(data=2, model=2)) == ("data",)


def test_batch_axes_data_mesh():
    assert shd.batch_axes(am(data=2, model=2), 4) == ("data",)
    assert shd.batch_axes(am(data=2, model=2), 3) == ()


# --------------------------------------------------------------------- #
# parameter + kv-pool rules
# --------------------------------------------------------------------- #

def test_param_pspec_model_axis_picks_priority_dim():
    spec = shd.param_pspec(("embed", "mlp"), (64, 128),
                           am(data=2, model=2), mode="serve")
    assert spec == P(None, "model")        # mlp outranks embed


def test_param_pspec_skips_indivisible_dims():
    # mlp=130 not divisible by 4: model falls through to embed
    spec = shd.param_pspec(("embed", "mlp"), (64, 130),
                           am(data=2, model=4), mode="serve")
    assert spec == P("model", None)


def test_param_pspec_train_adds_fsdp_serve_does_not():
    train = shd.param_pspec(("embed", "mlp"), (64, 128),
                            am(data=2, model=2), mode="train")
    serve = shd.param_pspec(("embed", "mlp"), (64, 128),
                            am(data=2, model=2), mode="serve")
    assert train == P("data", "model")
    assert serve == P(None, "model")


def test_kv_shard_axis_prefers_heads_then_pages():
    mesh = am(data=2, model=2)
    assert shd._kv_shard_axis(geo_stub(kv_heads=2), mesh) == "kv_heads"
    assert shd._kv_shard_axis(geo_stub(kv_heads=3), mesh) == "pages"
    assert shd._kv_shard_axis(
        geo_stub(kv_heads=3, hbm_pages=15), mesh) == "none"


def test_cache_shardings_specs():
    mesh = am(data=2, model=2)
    cs = shd.cache_shardings(geo_stub(kv_heads=2, batch=4), mesh)
    assert cs.k_hbm.spec == P(None, ("data",), None, None, "model", None)
    assert cs.hbm_owner.spec == P(None, ("data",), None)
    assert cs.page_table.spec == P(None, ("data",), None)
    assert cs.length.spec == P(("data",))
    # page-sharded fallback: model axis moves from kv_heads to pages
    cs = shd.cache_shardings(geo_stub(kv_heads=3, batch=4), mesh)
    assert cs.k_hbm.spec == P(None, ("data",), "model", None, None, None)
    assert cs.hbm_owner.spec == P(None, ("data",), "model")


@pytest.mark.parametrize("kv_heads", [2, 3])
def test_pool_pspec_is_cache_pool_layout_per_layer(kv_heads):
    """The layout the Pallas kernel is shard_mapped with is the one the
    cache is placed in, minus the layer dim."""
    mesh = am(data=2, model=2)
    geo = geo_stub(kv_heads=kv_heads, batch=4)
    spec = shd.pool_pspec(geo, mesh)
    assert P(None, *spec) == shd.cache_shardings(geo, mesh).k_hbm.spec


# --------------------------------------------------------------------- #
# serve-loop bundles
# --------------------------------------------------------------------- #

def test_policy_state_shardings_by_leaf_shape():
    mesh = am(data=2, model=2)
    geo = geo_stub(batch=4, num_layers=2, max_pages=8)
    state = {
        "last": jax.ShapeDtypeStruct((2, 4, 8), "int32"),    # [L, B, P]
        "lane": jax.ShapeDtypeStruct((4,), "int32"),         # [B]
        "bar": jax.ShapeDtypeStruct((), "float32"),          # scalar
    }
    sh = shd.policy_state_shardings(state, geo, mesh)
    assert sh["last"].spec == P(None, ("data",), None)
    assert sh["lane"].spec == P(("data",))
    assert sh["bar"].spec == P()
    assert shd.policy_state_shardings((), geo, mesh) == ()


def test_serve_shardings_bundle():
    mesh = am(data=2, model=2)
    sh = shd.serve_shardings(geo_stub(batch=4), mesh)
    assert sh["lane"].spec == P(("data",))
    assert sh["lane_kv"].spec == P(("data",), None)
    assert sh["step_lane"].spec == P(None, ("data",))
    assert sh["rep"].spec == P()
    assert sh["cache"].k_hbm.spec[4] == "model"


def test_serve_shardings_indivisible_lanes_replicate():
    sh = shd.serve_shardings(geo_stub(batch=3), am(data=2, model=2))
    assert sh["lane"].spec == P(())
    assert sh["cache"].length.spec == P(())


def test_real_trivial_mesh_accepted():
    # the concrete Mesh path (mesh.shape OrderedDict) on 1 device
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = shd.serve_shardings(geo_stub(batch=2), mesh)
    assert sh["lane"].spec == P(("data",))
    assert shd.batch_axes(mesh, 2) == ("data",)


def test_abstract_and_concrete_sizes_agree():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from repro.launch.mesh import mesh_axis_sizes
    assert mesh_axis_sizes(mesh) == {"data": 1, "model": 1}
    assert mesh_axis_sizes(am(data=2, model=2)) == \
        {"data": 2, "model": 2}


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
