"""The device this process computes on: established once, never assumed.

`ballista.executor.backend = "tpu"` is a request; whether a TPU answers it
is a fact about the process, and this module is where the fact is read.
`establish()` is called by `BallistaExecutor.start()` and by the first
`TaskContext.backend` read that says "tpu" (the one property every device
dispatch branches on), so the daemon, `StandaloneCluster` and an in-process
`ExecutionContext` all pass it before any program runs. It

- places JAX's persistent compilation cache (see `compile_cache_dir`);
- logs platform, device kind and device count;
- refuses a platform other than `tpu` unless CPU was asked for in so many
  words (`JAX_PLATFORMS=cpu`, which the test lane sets): a backend that
  silently came up on the host would otherwise run every "device" program
  and count every "device" counter on the CPU;
- refuses an HBM budget above what the device reports.

A chip belongs to one process, and this module initialises a JAX backend:
the scheduler and the client never import it.
"""

from __future__ import annotations

import logging
import os
import pathlib
from typing import NamedTuple, Optional

from ballista_tpu.errors import ExecutionError

log = logging.getLogger("ballista.device")


class DeviceInfo(NamedTuple):
    platform: str  # jax.devices()[0].platform
    device_kind: str  # jax.devices()[0].device_kind
    count: int  # len(jax.devices())
    # memory_stats()["bytes_limit"] of this process's first device; None
    # where the backend reports no memory statistics (CPU)
    bytes_limit: Optional[int]


class DeviceError(ExecutionError):
    """The process has no device the configuration can run on."""


# deliberately lock-free: written once with an atomic assignment. Two task
# threads racing the first call both ask JAX (whose backend initialisation
# is itself serialised), read the same answer and at worst log it twice.
_info: Optional[DeviceInfo] = None


def compile_cache_dir() -> str:
    """Where compiled programs persist: `JAX_COMPILATION_CACHE_DIR` when the
    environment sets it (JAX reads that variable itself, so the program
    leaves its configuration alone), else `<checkout>/.jax_cache`. The path
    is part of the cache key, so no other directory is ever set in code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env:
        return env
    return str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def _cpu_requested(jax) -> bool:
    # jax.config.jax_platforms defaults to the JAX_PLATFORMS variable and
    # also carries an explicit jax.config.update("jax_platforms", ...)
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def establish(config=None) -> DeviceInfo:
    """Initialise the JAX backend once and check it against `config`
    (platform and device-count checks run once; the HBM budget is checked
    for every config, since per-job settings may override it)."""
    global _info
    info = _info
    if info is None:
        import jax

        cache = compile_cache_dir()
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.makedirs(cache, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
        devices = jax.devices()
        d0 = devices[0]
        # in a multi-process run devices()[0] may belong to another
        # process, and only an addressable device reports its memory
        stats = jax.local_devices()[0].memory_stats() or {}
        info = DeviceInfo(
            d0.platform, d0.device_kind, len(devices),
            stats.get("bytes_limit"),
        )
        log.warning(
            "device established: platform=%s device_kind=%s count=%d "
            "bytes_limit=%s compile_cache=%s",
            info.platform, info.device_kind, info.count,
            info.bytes_limit, cache,
        )
        if info.platform != "tpu" and not _cpu_requested(jax):
            raise DeviceError(
                f"backend 'tpu' was configured but JAX came up on "
                f"platform {info.platform!r} ({info.device_kind}, "
                f"{info.count} device(s)): the chip is held by another "
                f"process or libtpu did not load. Set JAX_PLATFORMS=cpu "
                f"to run the device programs on the CPU on purpose."
            )
        _info = info
    if config is not None and info.bytes_limit is not None:
        budget = config.tpu_hbm_budget()
        if budget > info.bytes_limit:
            raise DeviceError(
                f"ballista.tpu.hbm_budget_bytes={budget} exceeds the "
                f"device's reported limit of {info.bytes_limit} bytes "
                f"({info.device_kind})"
            )
    return info


def reset() -> None:
    """Test hook: forget the established device so establish() runs again
    (the JAX backend itself stays initialised)."""
    global _info
    _info = None
