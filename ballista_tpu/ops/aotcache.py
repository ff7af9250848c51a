"""Persistent AOT compiled-program cache (ISSUE 8).

The in-memory shape-bucketed stage cache (ops/kernels.py) makes REPEATED
queries in one process cheap: jax.jit caches the compiled executable per
(program, shape bucket). A cold process still pays the Python trace + XLA
compile on its first query — which is most of a small query's latency, and
exactly what a serving tier cannot afford. This module adds the disk tier
beside the persisted layout cache (ops/layout_cache.py):

- On a fresh trace/compile, the jitted stage program is EXPORTED
  (jax.export: StableHLO + calling convention), serialized, and persisted
  under sha256(jax/jaxlib/backend fingerprint | stage identity | step name |
  static args | input tree + avals) — the stage-cache key's stable half
  (plan display + scan identity + config flags, no mtimes: programs are
  data-independent) plus the shape bucket.
- A later process's first call LOADS the artifact instead of tracing:
  deserialize + AOT-compile (jax.jit(exported.call).lower(avals).compile()),
  which skips the Python trace entirely and turns the XLA compile into a
  persistent-compilation-cache hit (ops/device.py places that cache).
- `prewarm()` walks the manifest at executor start and compiles every
  artifact BEFORE the first task arrives, so a cold executor's first small
  query runs with zero trace and zero compile (the latency harness asserts
  this through the serving counters).

Artifacts are integrity-checked: a corrupt blob, a deserialization failure,
or a fingerprint mismatch (different jax/jaxlib/backend than the writer)
falls back to a fresh trace/compile with the reason recorded
(serving counter `aot_load_error` + a warning log). The `aot.load` chaos
site tears disk loads deterministically to exercise exactly that path.

String-literal predicates are safe to cache across processes: literal codes
and LIKE/IN match tables ride as runtime `aux` arguments (ops/jaxexpr.py),
never as baked constants, so a reloaded program composes with whatever
dictionary state the loading process builds.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import tempfile
import threading
from typing import Dict, List, Optional, Tuple

from ballista_tpu.utils import tracing
from ballista_tpu.utils.locks import make_lock

log = logging.getLogger("ballista.tpu.aot")

# bump to orphan every persisted program (they are re-derived, not migrated)
_FORMAT = 1

_lock = make_lock("ops.aotcache._lock")
_dir: str = ""  # "" = disabled; guarded-by: _lock
_chaos = None  # guarded-by: _lock
# full key -> ("fresh", None) | ("disk"|"prewarm", compiled flat callable)
_mem: Dict[str, Tuple[str, object]] = {}  # guarded-by: _lock
_manifest_keys: Optional[set] = None  # lazily loaded; guarded-by: _lock
_fingerprint_cache: Optional[str] = None


def fingerprint() -> str:
    """jax/jaxlib/backend identity baked into every key AND every artifact:
    a program compiled by a different stack must never be trusted. Raises
    when no device can be established — an artifact keyed "unknown" would
    be shared by every platform that failed to come up."""
    global _fingerprint_cache
    if _fingerprint_cache is None:
        import jax
        import jaxlib

        from ballista_tpu.ops import device

        _fingerprint_cache = (
            f"v{_FORMAT}|jax{jax.__version__}|jaxlib{jaxlib.__version__}"
            f"|{device.establish().platform}"
        )
    return _fingerprint_cache


def configure(config) -> None:
    """Bind the cache directory + chaos injector from a config. Called on
    every kernel dispatch (cheap once set); the last configuration wins,
    like the layout cache's per-ctx directory resolution."""
    global _dir, _chaos
    d = config.tpu_aot_cache_dir()
    with _lock:
        if d != _dir:
            _dir = d
        from ballista_tpu.utils.chaos import chaos_from_config

        _chaos = chaos_from_config(config)


def reset(clear_disk_dir: bool = False) -> None:
    """Test hook: drop the in-memory program map (and optionally forget the
    configured directory) so a fresh process can be simulated."""
    global _dir, _chaos, _manifest_keys
    with _lock:
        _mem.clear()
        _manifest_keys = None
        if clear_disk_dir:
            _dir = ""
            _chaos = None


def _blob_path(base: str, key: str) -> str:
    return os.path.join(base, key[:2], key + ".jaxprog")


def _manifest_path(base: str) -> str:
    return os.path.join(base, "manifest.jsonl")


# holds-lock: _lock
def _load_manifest_keys_locked(base: str) -> set:
    global _manifest_keys
    if _manifest_keys is None:
        keys = set()
        try:
            with open(_manifest_path(base)) as f:
                for line in f:
                    try:
                        keys.add(json.loads(line)["key"])
                    except (json.JSONDecodeError, KeyError):
                        continue
        except OSError:
            pass
        _manifest_keys = keys
    return _manifest_keys


def manifest_entries(base: str) -> List[dict]:
    """All parseable manifest lines, newest-last, deduped by key."""
    out: Dict[str, dict] = {}
    try:
        with open(_manifest_path(base)) as f:
            for line in f:
                try:
                    e = json.loads(line)
                    out[e["key"]] = e
                except (json.JSONDecodeError, KeyError):
                    continue
    except OSError:
        return []
    return list(out.values())


def _save_artifact(base: str, key: str, name: str, blob: bytes) -> None:
    """Atomically persist one exported program + its manifest line.
    Best-effort: any failure leaves no partial entry and never raises."""
    try:
        target = _blob_path(base, key)
        if os.path.exists(target):
            return
        os.makedirs(os.path.dirname(target), exist_ok=True)
        meta = json.dumps({"fingerprint": fingerprint(), "name": name})
        payload = meta.encode() + b"\n" + blob
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(target), prefix=".wip-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(payload)
            os.replace(tmp, target)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        with _lock:
            keys = _load_manifest_keys_locked(base)
            if key not in keys:
                with open(_manifest_path(base), "a") as f:
                    f.write(json.dumps({"key": key, "name": name}) + "\n")
                keys.add(key)
        tracing.incr("serving.aot_saved")
    except Exception as e:
        log.debug("aot save failed (key=%s...): %s", key[:16], e)


def _read_artifact(base: str, key: str) -> Optional[bytes]:
    """Read + integrity-check one artifact; None (with the reason recorded)
    on corruption or fingerprint mismatch. The `aot.load` chaos site tears
    reads deterministically, keyed on the content-derived program key."""
    from ballista_tpu.utils.chaos import ChaosInjected

    path = _blob_path(base, key)
    if not os.path.exists(path):
        return None
    with _lock:
        chaos = _chaos
    try:
        if chaos is not None:
            chaos.maybe_fail("aot.load", f"prog:{key[:16]}")
        with open(path, "rb") as f:
            payload = f.read()
        header, _, blob = payload.partition(b"\n")
        meta = json.loads(header)
        if meta.get("fingerprint") != fingerprint():
            tracing.incr("serving.aot_load_error")
            log.warning(
                "aot artifact %s... rejected: fingerprint %r != %r "
                "(recompiling fresh)", key[:16], meta.get("fingerprint"),
                fingerprint(),
            )
            return None
        if not blob:
            raise ValueError("empty program blob")
        return blob
    except ChaosInjected as e:
        tracing.incr("serving.aot_load_error")
        log.warning("aot load torn by chaos (key=%s...): %s — recompiling "
                    "fresh", key[:16], e)
        return None
    except Exception as e:
        tracing.incr("serving.aot_load_error")
        log.warning("aot artifact %s... unreadable: %s — recompiling fresh",
                    key[:16], e)
        return None


def _compile_exported(blob: bytes, leaves_avals, name: str = "call"):
    """Deserialize an exported program and AOT-compile it for the flat
    calling convention. Raises on any mismatch (caller falls back)."""
    import jax
    from jax import export as jax_export

    exported = jax_export.deserialize(bytearray(blob))
    return jax.jit(_named(name, exported.call)).lower(*leaves_avals).compile()


def _named(name: str, fn):
    """`fn` under the step's name: the XLA module is `jit_<name>` and every
    op's metadata carries the scope, so a device trace says which program an
    operation belongs to. The name is the step's own, the same in every
    process (it is part of what the persistent compile cache keys on)."""
    import jax

    def step(*args):
        with jax.named_scope(name):
            return fn(*args)

    step.__name__ = step.__qualname__ = name
    return step


def _leaf_aval(leaf):
    import jax
    import numpy as np

    arr = leaf if hasattr(leaf, "dtype") else np.asarray(leaf)
    return jax.ShapeDtypeStruct(tuple(arr.shape), arr.dtype)


def wrap_step(owner, name: str, core, static_argnums: Tuple[int, ...] = (0,)):
    """Wrap one device-stage core in the AOT tier.

    Returns a callable with jax.jit semantics (same signature, including
    the static leading args). When the owner carries no `aot_key` (stage
    built outside the kernel dispatcher) or no cache dir is configured, the
    plain jitted function runs untouched. Otherwise each distinct
    (program, static args, input shapes) signature resolves through:
    in-memory compiled map -> disk artifact -> fresh trace/compile (which
    exports + persists the artifact for the next process), with the
    serving counters recording which tier served it.

    The returned callable also carries a ``.warm(*args)`` method:
    compile-WITHOUT-execute (ISSUE 19 satellite). It traces and
    XLA-compiles the signature via ``jit(...).lower(...).compile()`` —
    which primes jax's own executable cache, so the next real call is a
    cache hit — and registers/persists the AOT artifact, all without
    running the program: no output buffers are allocated and nothing is
    pinned past the compile. Background warmers (ops/sharedscan.py) use
    it so a warm-up never holds transient HBM outside the residency
    accounting."""
    import jax
    from jax.tree_util import tree_flatten, tree_unflatten

    core = _named(name, core)
    jitfn = jax.jit(core, static_argnums=static_argnums)
    static_set = frozenset(static_argnums)

    def signature(args):
        """(key, statics, treedef, leaves, avals) for an AOT-cacheable
        call, or None when the AOT tier must be bypassed (no cache dir,
        no owner key, or weak-typed leaves whose promotion semantics an
        exported strong aval could silently change)."""
        key_base = getattr(owner, "aot_key", None)
        with _lock:
            base = _dir
        if not base or key_base is None:
            return None
        statics = [(i, args[i]) for i in sorted(static_set)]
        dynamic = [a for i, a in enumerate(args) if i not in static_set]
        leaves, treedef = tree_flatten(tuple(dynamic))
        if any(bool(getattr(l, "weak_type", False)) for l in leaves):
            return None
        avals = [_leaf_aval(l) for l in leaves]
        sig = (
            f"{name}|s{[(i, repr(v)) for i, v in statics]!r}"
            f"|{treedef}|{[(a.shape, str(a.dtype)) for a in avals]!r}"
        )
        key = hashlib.sha256(
            f"{fingerprint()}|{key_base}|{sig}".encode()
        ).hexdigest()
        return key, statics, treedef, leaves, avals

    def export_and_save(key, statics, treedef, avals, n_args):
        """Export the traced program to StableHLO + persist it for the
        next process. Trace-only (stops at StableHLO — measured ~5% of a
        large unrolled program's XLA compile); never raises."""
        try:
            from jax import export as jax_export

            static_vals = dict(statics)

            def flat_fn(*flat_leaves):
                dyn = tree_unflatten(treedef, flat_leaves)
                full: List[object] = []
                di = 0
                for i in range(n_args):
                    if i in static_vals:
                        full.append(static_vals[i])
                    else:
                        full.append(dyn[di])
                        di += 1
                return core(*full)

            blob = bytes(jax_export.export(jax.jit(flat_fn))(*avals).serialize())
            with _lock:
                base = _dir
            if base:
                _save_artifact(base, key, name, blob)
        except Exception as e:
            log.debug("aot export failed (key=%s...): %s", key[:16], e)

    def wrapped(*args):
        # the host's side of one dispatch; the device runs on after it closes
        with tracing.span("runtime.launch", program=name) as launch:
            return dispatch(launch, args)

    def dispatch(launch, args):
        resolved = signature(args)
        if resolved is None:
            return jitfn(*args)
        key, statics, treedef, leaves, avals = resolved
        with _lock:
            base = _dir
            entry = _mem.get(key)
        if entry is not None:
            kind, compiled = entry
            tracing.incr("serving.compile_hit_memory")
            launch.set(tier="memory")
            if compiled is None:  # freshly traced this process: jit caches
                return jitfn(*args)
            out_flat = compiled(*leaves)
            return out_flat
        blob = _read_artifact(base, key)
        if blob is not None:
            try:
                compiled = _compile_exported(blob, avals, name)
                out_flat = compiled(*leaves)
            except Exception as e:
                tracing.incr("serving.aot_load_error")
                log.warning(
                    "aot artifact %s... failed to compile/run: %s — "
                    "recompiling fresh", key[:16], e,
                )
            else:
                with _lock:
                    _mem[key] = ("disk", compiled)
                tracing.incr("serving.compile_hit_disk")
                launch.set(tier="disk")
                return out_flat
        # fresh program: run the PLAIN jit first (its persistent-XLA-cache
        # key matches every compile this codebase ever did, so warm
        # deployments hit it), then export + serialize for the disk tier.
        # Compiling THROUGH the exported module here would key the
        # persistent XLA cache differently and recompile from scratch
        # (measured ~15s per big program, a whole-suite stall).
        tracing.incr("serving.compile_trace")
        launch.set(tier="trace")
        out = jitfn(*args)
        with _lock:
            _mem.setdefault(key, ("fresh", None))
        export_and_save(key, statics, treedef, avals, len(args))
        return out

    def warm(*args):
        """Compile this signature without executing it; True when a
        compile actually happened (False = already resolvable warm)."""
        resolved = signature(args)
        if resolved is None:
            # no AOT tier for this call: still prime jit's executable
            # cache so the next real call neither traces nor compiles
            jitfn.lower(*args).compile()
            tracing.incr("serving.compile_warmed")
            return True
        key, statics, treedef, leaves, avals = resolved
        with _lock:
            base = _dir
            if key in _mem:
                return False
        blob = _read_artifact(base, key)
        if blob is not None:
            try:
                compiled = _compile_exported(blob, avals, name)
            except Exception as e:
                tracing.incr("serving.aot_load_error")
                log.warning(
                    "aot artifact %s... failed to compile during warm: %s "
                    "— compiling fresh", key[:16], e,
                )
            else:
                with _lock:
                    _mem.setdefault(key, ("disk", compiled))
                tracing.incr("serving.compile_hit_disk")
                return True
        jitfn.lower(*args).compile()
        with _lock:
            _mem.setdefault(key, ("fresh", None))
        tracing.incr("serving.compile_warmed")
        export_and_save(key, statics, treedef, avals, len(args))
        return True

    wrapped.warm = warm
    return wrapped


def prewarm(config) -> int:
    """Load + AOT-compile every manifest artifact into the in-memory
    program map — run at executor start (ballista.tpu.prewarm) so the first
    small query's steps are compiled before the first task arrives. Returns
    the number of programs warmed; every failure is recorded and skipped
    (a stale artifact must never block executor start)."""
    configure(config)
    with _lock:
        base = _dir
    if not base:
        return 0
    import jax
    from jax import export as jax_export

    warmed = 0
    for entry in manifest_entries(base):
        key = entry.get("key")
        if not key:
            continue
        with _lock:
            if key in _mem:
                continue
        blob = _read_artifact(base, key)
        if blob is None:
            continue
        try:
            exported = jax_export.deserialize(bytearray(blob))
            compiled = (
                jax.jit(exported.call).lower(*exported.in_avals).compile()
            )
        except Exception as e:
            tracing.incr("serving.aot_load_error")
            log.warning("prewarm of %s... failed: %s", key[:16], e)
            continue
        with _lock:
            _mem[key] = ("prewarm", compiled)
        warmed += 1
        tracing.incr("serving.compile_prewarmed")
    if warmed:
        log.info("aot prewarm: %d compiled programs ready", warmed)
    return warmed
