"""SPMD stage programs over the virtual 8-device CPU mesh."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def mesh8():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from ballista_tpu.parallel.mesh import build_mesh

    return build_mesh({"data": 8})


def test_q1_style_psum_aggregate(mesh8):
    import jax.numpy as jnp

    from ballista_tpu.parallel.spmd import build_q1_style_step

    rng = np.random.default_rng(0)
    N, G = 4096, 6
    codes = rng.integers(0, G, N).astype(np.int32)
    qty = rng.uniform(1, 50, N).astype(np.float32)
    price = rng.uniform(900, 10_000, N).astype(np.float32)
    disc = rng.uniform(0, 0.1, N).astype(np.float32)
    tax = rng.uniform(0, 0.08, N).astype(np.float32)
    ship = rng.integers(8000, 10_500, N).astype(np.int32)

    step = build_q1_style_step(mesh8, G, cutoff_days=10_000)
    out = np.asarray(
        step(*(jnp.asarray(a) for a in (codes, qty, price, disc, tax, ship)))
    )
    assert out.shape == (6, G)

    m = ship <= 10_000
    ref_counts = np.zeros(G)
    np.add.at(ref_counts, codes[m], 1.0)
    np.testing.assert_allclose(out[0], ref_counts, rtol=1e-5)
    ref_qty = np.zeros(G)
    np.add.at(ref_qty, codes[m], qty[m])
    np.testing.assert_allclose(out[1], ref_qty, rtol=1e-4)
    ref_charge = np.zeros(G)
    np.add.at(ref_charge, codes[m], (price * (1 - disc) * (1 + tax))[m])
    np.testing.assert_allclose(out[4], ref_charge, rtol=1e-4)


def test_all_to_all_exchange_aggregate(mesh8):
    import jax.numpy as jnp

    from ballista_tpu.parallel.spmd import build_all_to_all_exchange_aggregate

    rng = np.random.default_rng(1)
    N, K = 4096, 64  # 64 keys over 8 shards -> 8 groups per shard
    keys = rng.integers(0, K, N).astype(np.int32)
    vals = rng.uniform(0, 1, N).astype(np.float32)

    ex = build_all_to_all_exchange_aggregate(mesh8)
    sums = np.asarray(ex(jnp.asarray(keys), jnp.asarray(vals), K // 8))

    ref = np.zeros(K)
    np.add.at(ref, keys, vals)
    # shard d owns keys with key % 8 == d, local group id = key // 8
    got_global = np.zeros(K)
    per_shard = sums.reshape(8, K // 8)
    for d in range(8):
        for g in range(K // 8):
            got_global[g * 8 + d] = per_shard[d, g]
    np.testing.assert_allclose(got_global, ref, rtol=1e-4)


def test_mesh_build_defaults():
    from ballista_tpu.parallel.mesh import build_mesh

    m = build_mesh()
    assert "data" in m.shape


def test_dryrun_multichip_inprocess():
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_dryrun_multichip_self_forces_platform():
    """The driver calls dryrun_multichip in a process with ONE device; the
    entry must force the virtual multi-device CPU platform itself
    (a one-device caller once got a one-device "mesh")."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    # child sees a 1-device CPU platform, like the driver's bare process
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    code = (
        f"import sys; sys.path.insert(0, {str(root)!r})\n"
        "import jax\n"
        "assert len(jax.devices()) == 1, jax.devices()\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(4)\n"
    )
    subprocess.run([sys.executable, "-c", code], env=env, check=True, cwd=root)
