"""`apply_migrations` inside `ops.sharded_pools` (the meshed serve
chunk's context) moves each shard's own pages under `shard_map`, as
`[T * KH, HD]` rows. Pinned bitwise to the plain executor over random
caches and collision-free random plans, on four host devices in a
process of their own (so the test runs on a one-device host too): a
data=1, model=4 mesh (heads sharded) and data=2, model=2 (lanes
sharded too, so each shard keeps only the rows of its own lanes).
"""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("mesh", ["1,4", "2,2"])
def test_sharded_migration_is_the_plain_one(mesh):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(HERE), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, HERE] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, __file__, mesh],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SAME 6" in proc.stdout, proc.stdout


def _check(data: int, model: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kvcache.migrate import apply_migrations
    from repro.kvcache.paged import CacheGeometry
    from repro.launch import shardings as shd
    from repro.launch.mesh import make_test_mesh
    from test_async_migration import _rand_cache, _rand_plan

    mesh = make_test_mesh(data=data, model=model)
    geo = CacheGeometry(num_layers=2, batch=4, page_tokens=4, hbm_pages=4,
                        host_pages=6, kv_heads=4, head_dim=8,
                        dtype=jnp.float32)
    cache_sh = shd.cache_shardings(geo, mesh)
    pool = shd.pool_pspec(geo, mesh)
    assert pool[3] == "model"

    def sharded(cache, plan):
        with ops.sharded_pools(mesh, pool):
            return apply_migrations(cache, plan)

    sharded = jax.jit(sharded, in_shardings=(cache_sh, None),
                      out_shardings=cache_sh)
    same = 0
    for seed in range(6):
        cache = _rand_cache(geo, seed)
        plan = _rand_plan(geo, 6, 100 + seed)
        want = apply_migrations(cache, plan)
        got = sharded(jax.device_put(cache, cache_sh), plan)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        same += 1
    return same


if __name__ == "__main__":
    d, m = (int(x) for x in sys.argv[1].split(","))
    print("SAME", _check(d, m))
