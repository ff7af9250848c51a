"""Measured cost model for adaptive execution (ISSUE 10).

Device-vs-host routing used to be a cascade of static admission checks
tuned blind (JOIN_MULTIPLICITY_TIERS, gather caps, the decline ladder in
ops/kernels.py). The bench already records the signals needed to do better
— per-config readback/ingest/join-path counters — and this module closes
the loop: observed costs feed back into routing decisions.

The store is a per-shape-bucket cost ledger persisted beside the layout
cache (ballista.tpu.cost_model_dir, default .ballista_cache/costmodel):

  entry key = op | engine | power-of-two units bucket
  entry     = {s: total seconds, units: total work units, n: observations}

ops in use: "join.gather" (units = padded gather elements), "join.host"
(units = build+probe rows), "h2d" / "readback" (units = bytes),
"compile|<step>" (units = 1), "stage.run|<stage id>" (units = the stage's
input size in leaf-file bytes or memory-scan rows, ISSUE 11 — normalized
so a rate learned at one scale predicts another; stage id is the sha1 of
the AOT stable stage key, so the store is keyed like the AOT cache on
stable stage identity), and "task.run|<shape>" under engine "task" (units
= 1; the SCHEDULER's per-stage task durations, keyed on the
job-id-scrubbed stage plan shape via task_run_op below — the rates behind
speculative-execution straggler detection), and "stage.batch" under engine
"task" (units = member count; the SCHEDULER's wall durations of shared-scan
batched tasks, ISSUE 13 — the evidence gate dispatches solo when a batch is
predicted slower than the members' solo task.run sum).

The store is two files, one per writer role, because a chip belongs to one
process. `costs.json` holds what an EXECUTOR measured (engines "device" and
"host") and carries the jax/jaxlib/platform fingerprint of the writer
(ops/aotcache.py::fingerprint): a store written by a different stack is
ignored wholesale — costs measured on another backend must never steer
this one. `tasks.json` holds what the SCHEDULER measured (engine "task":
wall durations of whole tasks as the control plane saw them) under a
fingerprint that names no platform, so the scheduler loads, predicts and
flushes without ever initialising a JAX backend — reading the platform
would take the chip from the executor beside it.

Prediction is rate-based: predict(op, engine, units) returns
units * (total_s / total_units), preferring the exact units bucket when it
has enough observations and falling back to the op-global rate. Updates
apply exponential forgetting (history halves once an entry saturates) so
the rate tracks the current machine, and a gross mispredict REPLACES the
bucket's history with the observed cost (`retier`) — the
mispredict-driven re-tiering that pulls an over-eager extended admission
back to the static ladder.

Decision discipline (bit-identity is the invariant): the cost model only
changes WHERE a partition runs, never what it returns, and the static
ladder remains both the cold-start prior and the hard safety cap — a cold
or corrupt store reproduces the pre-ISSUE-10 routing exactly.

Persistence is best-effort like the layout cache: atomic tmp+rename
writes, last-writer-wins per key across processes, corrupt or
fingerprint-mismatched files start an empty store (recorded via the
routing accumulator, never raised).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, Optional, Tuple
from ballista_tpu.utils.locks import make_lock

# bump to orphan every persisted entry (they are re-measured, not migrated).
# 2: stage.run units changed from 1 to input bytes/rows (ISSUE 11) — a
# pre-existing store's unit-less rates would predict file_bytes x
# seconds-per-run, a guaranteed gross mispredict per cached stage shape.
_FORMAT = 2
# engine -> store file: the scheduler's engine has a file of its own, which
# a process running no device code can read and write (module docstring)
_TASK_ENGINE = "task"
_BASENAMES = {"exec": "costs.json", "task": "tasks.json"}

# minimum observations before a rate is trusted for prediction
MIN_OBSERVATIONS = 4
# entry saturation: past this, history halves before each update so the
# rate follows the current machine instead of the all-time mean
_FORGET_AT = 32
# flush throttle: observe() persists at most this often (atexit + explicit
# flush() cover the tail)
_FLUSH_INTERVAL_S = 5.0
# observed/predicted ratio beyond which a decision counts as a mispredict
MISPREDICT_FACTOR = 3.0

_lock = make_lock("ops.costmodel._lock")
_dir: str = ""  # "" = in-memory only; guarded-by: _lock
# deliberately lock-free: a single bool written by configure()/reset() and
# read on hot paths (readback, h2d) — CPython bool loads are atomic and a
# stale read costs at most one missed/extra observation, never corruption
_enabled: bool = False
_loaded: set = set()  # domains ("exec"/"task") lazily loaded; guarded-by: _lock
_dirty: set = set()  # domains with unpersisted mutations; guarded-by: _lock
# bumped with every mutation; flush() only clears _dirty when the store it
# snapshotted is still current, so observations landing during an in-flight
# flush are never left unpersisted at exit; guarded-by: _lock
_gen: int = 0
_last_flush: float = 0.0  # guarded-by: _lock
# key -> {"s": float, "units": float, "n": int}; guarded-by: _lock
_store: Dict[str, Dict[str, float]] = {}
_atexit_registered = False


def _record_event(event: str, n: int = 1) -> None:
    from ballista_tpu.ops.runtime import record_routing_event

    record_routing_event(event, n)


def enabled() -> bool:
    """Cheap hot-path gate (bool read is atomic; staleness is harmless —
    the worst case is one missed or extra observation around configure)."""
    return _enabled


def configure(config) -> None:
    """Bind directory + enablement from a config, like the AOT cache. The
    last configuration wins; a directory change drops the in-memory store
    (entries lazily reload from the new path)."""
    global _dir, _enabled, _gen
    d = config.tpu_cost_model_dir()
    en = config.tpu_cost_model()
    global _atexit_registered
    global _last_flush
    with _lock:
        if d != _dir:
            _dir = d
            _store.clear()
            _gen += 1
            _loaded.clear()
            _dirty.clear()
            # start the flush throttle NOW: the first observation on a hot
            # path (readback, gather) must not pay a synchronous disk
            # round-trip; atexit + explicit flush() cover the tail
            _last_flush = time.monotonic()
        _enabled = en
        if not _atexit_registered:
            import atexit

            atexit.register(flush)
            _atexit_registered = True


def reset(clear_dir: bool = False) -> None:
    """Test hook: drop the in-memory store (and optionally forget the
    directory) so a fresh process can be simulated."""
    global _dir, _enabled, _gen
    with _lock:
        _store.clear()
        _gen += 1
        _loaded.clear()
        _dirty.clear()
        if clear_dir:
            _dir = ""
            _enabled = False


def _domain(engine: str) -> str:
    return "task" if engine == _TASK_ENGINE else "exec"


def _key_domain(key: str) -> str:
    # key = op|engine|b<bucket>; op may itself contain "|"
    return _domain(key.rsplit("|", 2)[1])


def _fingerprint(domain: str) -> str:
    if domain == "task":
        return f"cm{_FORMAT}|task"
    from ballista_tpu.ops import aotcache

    return f"cm{_FORMAT}|{aotcache.fingerprint()}"


def _bucket(units: float) -> int:
    """Power-of-two units bucket (recompilation-control analog: a bounded
    set of entries per op instead of one per distinct shape)."""
    b = 1
    u = max(1, int(units))
    while b < u:
        b <<= 1
    return b


def _key(op: str, engine: str, bucket: int) -> str:
    return f"{op}|{engine}|b{bucket}"


def task_run_op(shape: str) -> str:
    """Cost-store op for scheduler-side task durations of one stage shape
    (ISSUE 11). `shape` must already be job-independent (the caller scrubs
    the job id from the plan display) so repeated queries of the same shape
    share one rate across jobs — which is what lets the straggler monitor
    predict a fresh job's task cost from history."""
    import hashlib

    return "task.run|" + hashlib.sha1(shape.encode()).hexdigest()[:12]


# holds-lock: _lock
def _load_locked(engine: str) -> None:
    """Lazy-load the persisted file `engine` lives in. Corruption or a
    fingerprint mismatch starts empty with the reason recorded — a bad
    store must reproduce cold-start routing, never crash or steer."""
    domain = _domain(engine)
    if domain in _loaded:
        return
    _loaded.add(domain)
    if not _dir:
        return
    path = os.path.join(_dir, _BASENAMES[domain])
    loaded: Dict[str, Dict[str, float]] = {}
    try:
        with open(path) as f:
            blob = json.load(f)
        if (
            blob.get("format") != _FORMAT
            or blob.get("fingerprint") != _fingerprint(domain)
        ):
            _record_event("cost_store_fingerprint_mismatch")
            return
        for k, e in blob.get("entries", {}).items():
            s, units, n = float(e["s"]), float(e["units"]), int(e["n"])
            if s < 0 or units <= 0 or n <= 0 or _key_domain(k) != domain:
                raise ValueError(f"bad entry {k}")
            loaded[k] = {"s": s, "units": units, "n": n}
    except FileNotFoundError:
        return
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        _record_event("cost_store_corrupt")
        return
    _store.update(loaded)


def flush() -> None:
    """Best-effort atomic persist (tmp+rename) of every file with unsaved
    mutations. Merge policy is last-writer-wins per key: another process's
    entries for keys we never touched survive; shared keys take our value.
    Never raises."""
    global _last_flush
    with _lock:
        if not _dir or not _dirty:
            return
        domains = sorted(_dirty)
        entries = {k: dict(v) for k, v in _store.items()}
        base = _dir
        gen = _gen
    try:
        os.makedirs(base, exist_ok=True)
        for domain in domains:
            _write_domain(base, domain, {
                k: v for k, v in entries.items() if _key_domain(k) == domain
            })
        with _lock:
            if _gen == gen:
                _dirty.difference_update(domains)
            _last_flush = time.monotonic()
    except Exception:
        # still advance the throttle clock: an unwritable dir must not make
        # every subsequent observe() on a hot path re-attempt a full flush
        with _lock:
            _last_flush = time.monotonic()
        return


def _write_domain(base: str, domain: str, entries: dict) -> None:
    path = os.path.join(base, _BASENAMES[domain])
    fingerprint = _fingerprint(domain)
    merged = dict(entries)
    try:
        with open(path) as f:
            blob = json.load(f)
        if blob.get("format") == _FORMAT and blob.get("fingerprint") == fingerprint:
            for k, e in blob.get("entries", {}).items():
                merged.setdefault(k, e)
    except (OSError, ValueError, AttributeError):
        pass  # absent or unreadable: ours replaces it
    fd, tmp = tempfile.mkstemp(dir=base, prefix=".wip-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(
                {"format": _FORMAT, "fingerprint": fingerprint,
                 "entries": merged},
                f,
            )
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def observe(op: str, units: float, seconds: float, engine: str = "device") -> None:
    """Record one measured cost. No-op while the model is disabled, so hot
    paths (readback, h2d) can call unconditionally."""
    if not _enabled or seconds < 0 or units <= 0:
        return
    global _last_flush, _gen
    k = _key(op, engine, _bucket(units))
    with _lock:
        _load_locked(engine)
        e = _store.get(k)
        if e is None:
            _store[k] = {"s": float(seconds), "units": float(units), "n": 1}
        else:
            if e["n"] >= _FORGET_AT:
                e["s"] *= 0.5
                e["units"] *= 0.5
                e["n"] = e["n"] // 2
            e["s"] += float(seconds)
            e["units"] += float(units)
            e["n"] += 1
        _dirty.add(_domain(engine))
        _gen += 1
        due = _dir and time.monotonic() - _last_flush > _FLUSH_INTERVAL_S
        if due:
            # claim the flush slot under the lock so a burst of observes
            # spawns ONE writer, then persist off the hot path — a device
            # readback must never wait on a disk round-trip
            _last_flush = time.monotonic()
    if due:
        threading.Thread(
            target=flush, daemon=True, name="costmodel-flush"
        ).start()


def seed(op: str, units: float, seconds: float, engine: str = "device",
         n: int = MIN_OBSERVATIONS) -> None:
    """Directly install a warm entry (tests + the fuzz slice's adversarial
    entries). Replaces any history for the bucket."""
    global _gen
    with _lock:
        _load_locked(engine)
        _store[_key(op, engine, _bucket(units))] = {
            "s": float(seconds), "units": float(units), "n": int(n),
        }
        _dirty.add(_domain(engine))
        _gen += 1


def retier(op: str, units: float, seconds: float, engine: str = "device") -> None:
    """Mispredict-driven re-tiering: REPLACE the bucket's history with the
    observed cost, so the very next prediction reflects reality instead of
    averaging the surprise away."""
    if not _enabled:
        return
    global _gen
    with _lock:
        _load_locked(engine)
        _store[_key(op, engine, _bucket(units))] = {
            "s": float(seconds), "units": float(units), "n": MIN_OBSERVATIONS,
        }
        _dirty.add(_domain(engine))
        _gen += 1
    _record_event("retier")


def gross_mispredict(predicted: float, observed: float) -> bool:
    """True when observed deviates from predicted by MISPREDICT_FACTOR in
    EITHER direction — the one accounting definition shared by the routing
    mispredict counter and the re-tiering below."""
    return (
        observed > MISPREDICT_FACTOR * predicted
        or observed * MISPREDICT_FACTOR < predicted
    )


def check_mispredict(op: str, units: float, predicted: Optional[float],
                     observed: float, engine: str = "device") -> bool:
    """Canonical post-decision check: a gross mispredict (either way)
    re-tiers the bucket so the next prediction reflects reality. Returns
    whether it fired. Every consumer that predicted a cost runs this —
    one implementation, so no site can drift to a one-sided check."""
    if predicted is None or not gross_mispredict(predicted, observed):
        return False
    retier(op, units, observed, engine=engine)
    return True


@contextmanager
def timed(op: str, units: float = 1.0, engine: str = "device",
          routing_op: Optional[str] = None,
          predictive: bool = True) -> Iterator[None]:
    """Time the body as one measured decision — the single implementation
    of the predict/observe/record-routing/re-tier accounting contract, so
    no call site can drift to a partial or one-sided variant. A body
    exception skips the accounting entirely (a failed attempt is not an
    observation of the op's cost). `routing_op` additionally records the
    decision in the routing accumulator under `engine`; predictive=False
    degrades to a plain timed observation (the host-side alternative-cost
    probes, which must not re-tier)."""
    predicted = predict(op, units, engine=engine) if predictive else None
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    observe(op, units, dt, engine=engine)
    if routing_op is not None:
        from ballista_tpu.ops.runtime import record_routing

        record_routing(engine, routing_op, predicted, dt)
    if predictive:
        check_mispredict(op, units, predicted, dt, engine=engine)


def rate(op: str, engine: str = "device") -> Optional[Tuple[float, int]]:
    """Op-global (seconds per unit, observation count) across buckets, or
    None when nothing was observed."""
    prefix = f"{op}|{engine}|b"
    with _lock:
        _load_locked(engine)
        s = units = 0.0
        n = 0
        for k, e in _store.items():
            if k.startswith(prefix):
                s += e["s"]
                units += e["units"]
                n += int(e["n"])
    if n == 0 or units <= 0:
        return None
    return s / units, n


def bucket_rate(op: str, units: float, engine: str = "device") -> Optional[float]:
    """Seconds per unit of the EXACT power-of-two bucket covering `units`,
    or None when the bucket is cold (< MIN_OBSERVATIONS) or the model is
    off. Unlike predict(), never falls back to the op-global rate — the
    h2d chunk picker (ops/runtime.py) compares candidate buckets against
    each other, and the global fallback would make every candidate tie."""
    if not _enabled:
        return None
    k = _key(op, engine, _bucket(units))
    with _lock:
        _load_locked(engine)
        e = _store.get(k)
        if e is None or e["n"] < MIN_OBSERVATIONS or e["units"] <= 0:
            return None
        return e["s"] / e["units"]


def predict(op: str, units: float, engine: str = "device") -> Optional[float]:
    """Predicted seconds for `units` of `op` on `engine`: the exact units
    bucket when it has MIN_OBSERVATIONS, else the op-global rate, else None
    (cold — callers fall back to the static prior)."""
    if not _enabled:
        return None
    k = _key(op, engine, _bucket(units))
    with _lock:
        _load_locked(engine)
        e = _store.get(k)
        if e is not None and e["n"] >= MIN_OBSERVATIONS and e["units"] > 0:
            return units * e["s"] / e["units"]
    r = rate(op, engine)
    if r is None or r[1] < MIN_OBSERVATIONS:
        return None
    return units * r[0]


def snapshot() -> Dict[str, Dict[str, float]]:
    """Copy of the in-memory store, both files loaded (tests/diagnostics
    in a process that may touch the device)."""
    with _lock:
        _load_locked("device")
        _load_locked(_TASK_ENGINE)
        return {k: dict(v) for k, v in _store.items()}
