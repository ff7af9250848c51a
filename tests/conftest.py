"""Test harness config.

JAX tests run on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count), the strategy for validating
multi-chip sharding without TPU pods. Must run before jax is imported.
"""

import os

# Must happen before jax initializes a backend: the suite asks for the CPU
# in so many words (ops/device.py refuses backend "tpu" on a platform
# nobody asked for), with eight virtual devices for the mesh tests.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import pyarrow as pa
import pytest

# The persisted layout cache defaults to a cwd-relative directory; tests
# must not leave cache trees in the working copy (cache-specific tests pass
# an explicit tmp dir instead).
import ballista_tpu.config as _config

_config.DEFAULT_SETTINGS[_config.BALLISTA_TPU_LAYOUT_CACHE_DIR] = ""
# Same for the ISSUE 10 cost store: adaptive routing stays ON (the
# structural paths — splits, skew re-plans, build swaps — are exercised by
# the whole suite) but observations never persist across test runs.
_config.DEFAULT_SETTINGS[_config.BALLISTA_TPU_COST_MODEL_DIR] = ""


@pytest.fixture(autouse=True)
def _fresh_cost_store():
    """The in-memory cost store is process-global and configure() only
    clears it on a DIRECTORY change — with the dir pinned to "" above,
    observations would otherwise accumulate across every test in the
    process, and a test's routing (extended tiers, predictions) would
    depend on which device joins happened to run before it. Dropping the
    store per test keeps routing deterministic under any ordering/subset;
    tests that want a warm store seed it explicitly."""
    from ballista_tpu.ops import costmodel

    costmodel.reset(clear_dir=True)
    yield


@pytest.fixture
def sales_table() -> pa.Table:
    """Small deterministic table used across operator tests."""
    return pa.table(
        {
            "id": pa.array(list(range(10)), type=pa.int64()),
            "region": pa.array(
                ["east", "west", "east", "north", "west",
                 "east", "north", "west", "east", "west"]
            ),
            "amount": pa.array(
                [10.0, 20.0, 30.0, 5.0, 15.0, 25.0, 35.0, 45.0, 55.0, 65.0]
            ),
            "qty": pa.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], type=pa.int32()),
        }
    )


@pytest.fixture
def ctx():
    from ballista_tpu.engine import ExecutionContext

    return ExecutionContext()


# -- multi-process collective capability probe ------------------------------
# Some CPU jax builds cannot compile cross-process collectives ("Multiprocess
# computations aren't implemented on the CPU backend"): the two-process
# test_multihost mesh tests then fall back to path="host" and fail on the
# path assertion — an environment limit, not a code regression (ROADMAP).
# Probe ONCE per session with a real 2-process shard_map psum (the exact
# mechanism the production pod path uses) and let those tests skip cleanly.
# TPU images (and CPU builds with working Gloo collectives) pass the probe,
# so real mesh-path regressions still fail loudly there.

_MP_PROBE_SCRIPT = r"""
import sys

pid, port = int(sys.argv[1]), sys.argv[2]
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
sys.path.insert(0, sys.argv[3])

import jax

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    f"127.0.0.1:{port}", num_processes=2, process_id=pid
)

import numpy as np
from jax.sharding import PartitionSpec as P

from ballista_tpu.parallel import multihost as mh
from ballista_tpu.parallel.mesh import build_mesh
from jax import shard_map

n = len(jax.devices())
mesh = build_mesh({"data": n})
blocks = {i: np.ones(4, np.float32) for i in mh.local_shard_ids(mesh)}
g = mh.make_sharded(mesh, blocks, 4 * n, np.float32)
fn = jax.jit(shard_map(
    lambda x: jax.lax.psum(x.sum(), "data"),
    mesh=mesh, in_specs=(P("data"),), out_specs=P(), check_vma=False,
))
out = float(np.asarray(fn(g)))
assert out == 4.0 * n, out
print("MULTIPROCESS_OK")
"""

_mp_probe_result = None


def multiprocess_collectives_supported() -> bool:
    """Session-cached 2-process probe; True when the backend can run the
    production multi-process mesh program."""
    global _mp_probe_result
    if _mp_probe_result is not None:
        return _mp_probe_result
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    repo = str(pathlib.Path(__file__).resolve().parent.parent)
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(_MP_PROBE_SCRIPT)
        script = f.name
    procs = [
        subprocess.Popen(
            [_sys.executable, script, str(pid), str(port), repo],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in range(2)
    ]
    ok = True
    backend_limit = False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out = ""
        ok = ok and p.returncode == 0 and "MULTIPROCESS_OK" in (out or "")
        if "Multiprocess computations aren't implemented" in (out or ""):
            backend_limit = True
    try:
        os.unlink(script)
    except OSError:
        pass
    # Skip ONLY on the known backend limit. Any other probe failure (a
    # regression in make_sharded/build_mesh, a timeout, a port
    # clash) reports "supported" so the real tests RUN and fail loudly
    # instead of silently skipping a production regression.
    _mp_probe_result = ok or not backend_limit
    return _mp_probe_result


@pytest.fixture(scope="session")
def multiprocess_mesh():
    """Skip (not fail) multi-process mesh-path tests on backends that cannot
    compile cross-process collectives."""
    if not multiprocess_collectives_supported():
        pytest.skip(
            "backend cannot run 2-process collectives "
            "(\"Multiprocess computations aren't implemented\") — "
            "environment limit, see ROADMAP"
        )
