"""The device is established, never assumed (ops/device.py), and a chip
belongs to one process: what keeps the chip bring-up from rotting, checked
on the CPU lane."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from ballista_tpu.config import BallistaConfig
from ballista_tpu.ops import device

REPO = pathlib.Path(__file__).resolve().parent.parent
TPU = {"ballista.executor.backend": "tpu"}


@pytest.fixture
def fresh_device():
    device.reset()
    yield
    device.reset()


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"
    id = 0

    def memory_stats(self):
        return {"bytes_limit": 16 << 30}


def test_tpu_backend_refuses_a_platform_nobody_asked_for(monkeypatch, fresh_device):
    """Backend tpu, JAX up on the CPU, and JAX_PLATFORMS=cpu NOT given: the
    chip is held elsewhere or libtpu did not load. Both entry points refuse
    instead of running "device" programs on the host."""
    from ballista_tpu.executor.runtime import BallistaExecutor
    from ballista_tpu.physical.plan import TaskContext

    monkeypatch.setattr(device, "_cpu_requested", lambda jax: False)
    with pytest.raises(device.DeviceError, match="JAX came up on platform 'cpu'"):
        TaskContext(config=BallistaConfig(TPU)).backend
    ex = BallistaExecutor("127.0.0.1", 1, config=BallistaConfig(TPU))
    try:
        with pytest.raises(device.DeviceError):
            ex.start()
        assert not ex._flight_thread.is_alive()  # refused before serving
    finally:
        ex.scheduler_client.close()
    # the host backend asks for no device and is not held to one
    assert TaskContext(config=BallistaConfig()).backend == "cpu"


def test_cpu_asked_for_in_so_many_words_is_legal(fresh_device):
    from ballista_tpu.physical.plan import TaskContext

    assert TaskContext(config=BallistaConfig(TPU)).backend == "tpu"
    info = device.establish()
    assert (info.platform, info.bytes_limit) == ("cpu", None)


def test_hbm_budget_above_the_device_limit_is_an_error(monkeypatch, fresh_device):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(jax, "local_devices", lambda *a: [_FakeTpu()])
    info = device.establish(BallistaConfig(TPU))  # 12 GiB default fits 16
    assert (info.platform, info.device_kind, info.count) == ("tpu", "TPU v5 lite", 1)
    too_big = BallistaConfig({**TPU, "ballista.tpu.hbm_budget_bytes": str(32 << 30)})
    with pytest.raises(device.DeviceError, match="exceeds the device's reported limit"):
        device.establish(too_big)


def test_aot_fingerprint_raises_instead_of_unknown(monkeypatch, fresh_device):
    import jax

    from ballista_tpu.ops import aotcache

    def no_backend(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(aotcache, "_fingerprint_cache", None)
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        aotcache.fingerprint()


def test_kernel_import_failure_is_not_a_host_fallback(monkeypatch):
    """ops/dispatch.py used to map an ImportError under ops/kernels.py to
    "host path", silently."""
    import pyarrow as pa

    from ballista_tpu.ops import dispatch

    import ballista_tpu.ops as ops_pkg

    # gone from sys.modules AND from the package, or `from ballista_tpu.ops
    # import kernels` would still find an earlier test's import
    monkeypatch.setitem(sys.modules, "ballista_tpu.ops.kernels", None)
    monkeypatch.delattr(ops_pkg, "kernels", raising=False)
    with pytest.raises(ImportError):
        dispatch.tpu_filter(pa.record_batch({"a": [1]}), None)


_CACHE_PROBE = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    import jax
    from ballista_tpu.ops import device
    device.establish()
    placed = jax.config.jax_compilation_cache_dir
    # every program persists, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()
    print(json.dumps({{"placed": placed, "reported": device.compile_cache_dir()}}))
""")


def _cache_probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE.format(repo=str(REPO))],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_from_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program leaves JAX's cache
    directory alone, and compiled programs appear there."""
    d = tmp_path / "placed"
    got = _cache_probe(str(d))
    assert got == {"placed": str(d), "reported": str(d)}
    assert any(p.name.endswith("-cache") for p in d.iterdir())


def test_compile_cache_defaults_to_the_checkout():
    default = str(REPO / ".jax_cache")
    assert _cache_probe(None) == {"placed": default, "reported": default}


_SCHEDULER_AND_CLIENT = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.scheduler.kv import MemoryBackend
    from ballista_tpu.scheduler.server import SchedulerServer, serve

    # default config: the cost store lives at .ballista_cache/costmodel
    impl = SchedulerServer(MemoryBackend())
    server = serve(impl, "127.0.0.1", {port})
    print("READY", flush=True)
    ctx = BallistaContext("127.0.0.1", {port})
    ctx.register_parquet("t", {data!r})
    out = ctx.sql("select g, sum(v) as s from t group by g order by g").collect()
    ctx.close()
    from ballista_tpu.ops import costmodel
    costmodel.flush()
    jax_imported = "jax" in sys.modules
    from jax._src import xla_bridge
    print(json.dumps({{
        "rows": out.num_rows, "sums": out.column("s").to_pylist(),
        "jax_imported": jax_imported,
        "backends_initialized": xla_bridge.backends_are_initialized(),
        "store": sorted(os.listdir(".ballista_cache/costmodel")),
    }}), flush=True)
    server.stop(0)
""")


def test_scheduler_and_client_never_initialise_a_jax_backend(tmp_path):
    """A scheduler process and a client process's worth of code (one child
    here) plan a two-stage job, observe its task durations and flush the
    cost store under the DEFAULT cost-model directory; the executor is this
    test process. The child must end with no JAX backend initialised — on a
    TPU host it would otherwise take the chip from the executor — and its
    observations live in tasks.json, the file that names no platform."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from ballista_tpu.executor.runtime import BallistaExecutor, _free_port

    data = tmp_path / "t"
    data.mkdir()
    rng = np.random.default_rng(3)
    want = np.zeros(4, dtype=np.int64)
    for i in range(3):  # three files: three scan partitions and a shuffle
        g = rng.integers(0, 4, 500)
        v = rng.integers(0, 100, 500)
        np.add.at(want, g, v)
        pq.write_table(pa.table({"g": g, "v": v}), data / f"part-{i}.parquet")
    port = _free_port()
    child = subprocess.Popen(
        [sys.executable, "-c",
         _SCHEDULER_AND_CLIENT.format(repo=str(REPO), port=port, data=str(data))],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    executor = None
    try:
        assert child.stdout.readline().strip() == "READY", child.stderr.read()[-2000:]
        executor = BallistaExecutor("127.0.0.1", port)
        executor.start()
        stdout, stderr = child.communicate(timeout=120)
    finally:
        if executor is not None:
            executor.stop()
        if child.poll() is None:
            child.kill()
            child.communicate()
    assert child.returncode == 0, stderr[-2000:]
    got = json.loads(stdout.strip().splitlines()[-1])
    assert got["rows"] == 4 and got["sums"] == want.tolist()
    assert got["backends_initialized"] is False
    assert got["jax_imported"] is False
    assert got["store"] == ["tasks.json"]
    blob = json.loads((tmp_path / ".ballista_cache/costmodel/tasks.json").read_text())
    assert blob["fingerprint"].endswith("|task") and blob["entries"]
    assert all("|task|" in k for k in blob["entries"])


def test_chip_smoke_refuses_without_an_accelerator_or_a_checkout(tmp_path):
    """The driver's contract: no accelerator -> non-zero and no result line
    (JAX_PLATFORMS=cpu alone does not make it a dry run), and the same in a
    directory that holds chip_smoke.py and nothing else of the repository."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120,
                         cwd=tmp_path)
    assert out.returncode == 3 and "no accelerator" in out.stderr
    assert '"ok"' not in out.stdout
    alone = tmp_path / "alone"
    alone.mkdir()
    (alone / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         capture_output=True, text=True, timeout=120, cwd=alone)
    assert out.returncode == 2 and "not inside a checkout" in out.stderr
    assert out.stdout == ""


def test_chip_smoke_dry_run_from_committed_files_only(tmp_path):
    """chip_smoke.py end to end at SF=0.01 as an explicitly labelled CPU dry
    run, from a tree that holds only what git would commit: no pre-built
    native library, no pre-filled cache. It prints no time."""
    tree = tmp_path / "tree"
    tree.mkdir()
    files = subprocess.run(
        ["git", "ls-files", "-z", "--cached", "--others", "--exclude-standard"],
        cwd=REPO, capture_output=True, check=True,
    ).stdout.split(b"\0")
    for rel in filter(None, files):
        rel = rel.decode()
        src = REPO / rel
        if not src.is_file() or rel.startswith("tests/"):
            continue
        dst = tree / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_bytes(src.read_bytes())
    assert not list(tree.rglob("*.so")) and not (tree / ".jax_cache").exists()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)  # one device: the suite's eight are virtual
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--dry-run", "--sf", "0.01", "--parts", "2"],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "dry_run": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert "CPU DRY RUN" in lines[0]
    assert "seconds" not in out.stdout.replace("host seconds", "")
    assert "compile_s" not in out.stdout
    assert "four-chip leg skipped: 1 device(s)" in out.stdout
    assert f"compile_cache_dir={tree / '.jax_cache'}" in out.stdout
    assert any((tree / ".jax_cache").iterdir())
    for name in ("q1", "q3", "q5", "q6", "q10", "q12"):
        assert f"[served] oracle {name}: exact=" in out.stdout
