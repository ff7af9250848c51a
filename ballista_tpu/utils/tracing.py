"""Spans and counters of the served path: one recorder, always on.

    with span("scheduler.plan", job=job_id):
        ...
    record("scheduler.queue", t_runnable_ns, t_handed_ns, job=j, stage=s, partition=p)
    print(timeline(job_id))

A span keeps its name, start and end (`time.perf_counter_ns`), the thread,
its parent (the innermost span open on the same thread, or `parent=`) and
the request's identifiers `job`, `stage`, `partition`, which a child takes
from its parent where it is given none. Closing a span is one append to a
bounded ring, with no lock: the oldest span falls out and is counted in
`tracing.dropped`. `_mu` guards the counters and the swap in `reset()`.

While JAX is imported in the process and a profiler session is on, a span
is also a `jax.profiler.TraceAnnotation` and lies in the `.xplane.pb` on the
device's time line; without a session that is one flag test.

`reset()` keeps what it clears; `drained()` returns it. `by_name` and
`covered_s` are what the readers of a log share.

A counter's name is `<family>.<event>`. `device.`, `spmd.` and `serde.` are
the served path's, and the benchmark prints the first two. Nine families
count control-plane events; `counters("<family>", reset=True)` reads and
clears one of them alone, and only tests do:

recovery, in scheduler/{state,server,rpc}, client/*, executor/*, distributed/stages, utils/chaos:
    task_retry, fetch_failed, map_recomputed, lost_task_reset, stale_status_dropped, rpc_retry,
    plan_retry, ownership_redirected, lease_* and restart_* (a scheduler's recover());
    chaos_injected with every injected fault, and chaos_<what> beside it
tenancy, in scheduler/{state,server}, client/context:
    the result cache's cache_hit / _miss / _put / _put_torn / _expired / _evicted / _invalidated /
    _unkeyable / _lost_resubmitted; plan_cache_hit; admit_quota_deferred, admit_slo_boosted,
    speculate_quota_deferred
serving, in scheduler/server, executor/execution_loop, client/context, ops/aotcache:
    dispatch_push / dispatch_poll (how a task reached its executor), push_subscribed,
    push_stream_drop, task_pushed; the client's status_push* and stream_partition_early;
    compile_trace / _hit_memory / _hit_disk / _prewarmed / _warmed, aot_saved, aot_load_error
speculation, in scheduler/state:
    launched, relaunched, won, lost, failed, promoted, orphaned, executor_lost, restored,
    superseded_won / _failed; wasted_seconds (a float: duplicated compute thrown away);
    slo_met / slo_misses (jobs that ended within or past their tenant's slo_ms)
shared_scan, in scheduler/state (a batch forms) and ops/sharedscan (it runs):
    batches_formed, batched_stages, batch_gate_solo, batch_chaos_solo; shared_groups,
    uploads_saved, launches_saved, device_launches, warm_fallback_launches, member_ineligible,
    member_degraded, batch_degraded
shuffle_tier, in distributed/stages, client/context, scheduler/state:
    storage_publish / local_publish, storage_fetch / peer_fetch, storage_fallback_peer,
    storage_publish_torn / _read_torn, client_storage_fetch / _miss, gc_stage_swept / _result_swept
exchange, in ops/exchange, distributed/stages, executor/flight_service, scheduler/state:
    published, publish_bytes, reupload_skipped, h2d_bytes_saved, served_from_registry,
    d2h_bytes_saved, miss, skipped_budget, evicted_budget / _tenant_budget / _chaos,
    locality_preferred
delta, in ops/stage (chunks) and scheduler/server (a cached result advanced):
    chunks_reused, chunks_prepared, bytes_reprepared_saved, save_declined_midappend,
    advance_hits, advance_declined
fleet, in executor/{runtime,execution_loop}:
    evaluations, scale_up, scale_down, scale_chaos_skipped, drain_completed, drain_timeout
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from ballista_tpu.utils.locks import make_lock

# The largest window of today's cells closes about 39k spans (185 queries of
# `scan_agg` in 51 s, some 210 a query; my chip run, PR 26): four of them fit.
RING = 1 << 18


class _Log:
    """The ring of closed spans and the count of those that fell out of it."""

    __slots__ = ("ring", "overflow", "dropped")

    def __init__(self) -> None:
        self.ring: "collections.deque[Span]" = collections.deque(maxlen=RING)
        self.overflow = itertools.count(1)
        self.dropped = 0


_local = threading.local()
_ids = itertools.count(1)
_log = _Log()  # swapped whole by reset(); appended to with no lock
_counters: Dict[str, float] = {}  # guarded-by: _mu
_last: Dict[str, object] = {"spans": [], "counters": {}}  # guarded-by: _mu
_mu = make_lock("utils.tracing._mu")


class Span:
    """One interval of one thread's work for one request."""

    __slots__ = ("name", "start_ns", "end_ns", "tid", "id", "parent", "job",
                 "stage", "partition", "attrs", "_annotation")

    def __init__(self, name: str, job=None, stage=None, partition=None,
                 parent: Optional["Span"] = None, attrs: Optional[dict] = None):
        self.name = name
        self.job, self.stage, self.partition = job, stage, partition
        self.parent = parent.id if parent is not None else 0
        self.attrs = attrs or {}
        self.id = next(_ids)
        self.tid = threading.get_ident()
        self.start_ns = self.end_ns = 0
        self._annotation = None
        if parent is not None:
            self._adopt(parent)

    def _adopt(self, parent: "Span") -> None:
        if self.job is None:
            self.job = parent.job
        if self.stage is None:
            self.stage = parent.stage
        if self.partition is None:
            self.partition = parent.partition

    def set(self, **attrs) -> None:
        """Attributes known only once the work is under way (`bytes`, `via`)."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        if stack and not self.parent:
            self.parent = stack[-1].id
            self._adopt(stack[-1])
        annotate = _annotate or _find_annotate()
        if annotate is not None and annotate.is_enabled():  # a profiler session is on
            ids = {k: v for k, v in (("job", self.job), ("stage", self.stage),
                                     ("partition", self.partition)) if v is not None}
            self._annotation = annotate(self.name, **ids)
            self._annotation.__enter__()
        stack.append(self)  # last: nothing above may leave it there unpopped
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        _local.stack.pop()
        _close(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.seconds * 1e3:.3f} ms, job={self.job!r}, "
                f"stage={self.stage!r}, partition={self.partition!r}, {self.attrs})")


_annotate = None  # jax.profiler.TraceAnnotation, once JAX is there to ask


def _find_annotate():
    """`jax.profiler.TraceAnnotation` if JAX is imported in this process, and
    never the start of that import. `jax` is in sys.modules from the first
    line of another thread's `import jax`, seconds before `jax.profiler`
    exists: until it does, spans are recorded and not annotated."""
    global _annotate
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    _annotate = getattr(profiler, "TraceAnnotation", None)
    return _annotate


def _close(s: Span) -> None:
    log = _log
    if len(log.ring) == RING:
        log.dropped = next(log.overflow)
    log.ring.append(s)


def span(name: str, job=None, stage=None, partition=None,
         parent: Optional[Span] = None, **attrs) -> Span:
    """A block of work: `with span(...) as s:`; `s.job = ...` and `s.set(...)`
    fill in what is known only inside the block."""
    return Span(name, job, stage, partition, parent, attrs)


def record(name: str, start_ns: int, end_ns: int, job=None, stage=None,
           partition=None, parent: Optional[Span] = None, **attrs) -> Span:
    """An interval whose two ends lie in different calls or threads."""
    s = Span(name, job, stage, partition, parent, attrs)
    s.start_ns, s.end_ns = start_ns, max(start_ns, end_ns)
    _close(s)
    return s


def current() -> Optional[Span]:
    """The innermost span open on this thread: what a worker thread is given
    as `parent=` so that its spans join the request's tree."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def adopt(parent: Optional[Span]):
    """On a helper thread: spans opened inside are children of `parent`, a
    span open on the thread it works for (a reader thread that pulls a
    generator on behalf of the stage that consumes it)."""
    if parent is None:
        yield
        return
    try:
        stack = _local.stack
    except AttributeError:
        stack = _local.stack = []
    stack.append(parent)
    try:
        yield
    finally:
        stack.pop()


def now_ns() -> int:
    return time.perf_counter_ns()


def spans() -> List[Span]:
    """The spans closed since the last `reset()`, oldest first."""
    return list(_log.ring)


def incr(name: str, by: float = 1) -> None:
    """Monotonic named counter (e.g. spmd.mesh vs spmd.host_fallback, so a
    permanently-broken mesh path is visible in ops, not just test asserts).
    `by` is a count, or seconds where the name says so."""
    with _mu:
        _counters[name] = _counters.get(name, 0) + by


def _with_dropped(counts: Dict[str, float], log: _Log) -> Dict[str, float]:
    if log.dropped:
        counts["tracing.dropped"] = log.dropped
    return counts


def counters(family: Optional[str] = None, reset: bool = False) -> Dict[str, float]:
    """{name: n} of every counter; with a family, {event: n} of the names
    `<family>.<event>`. `reset=True` clears the names returned and no other."""
    prefix = f"{family}." if family else ""
    with _mu:
        out = {k[len(prefix):]: v for k, v in _counters.items() if k.startswith(prefix)}
        if reset:
            for event in out:
                del _counters[prefix + event]
        return out if family else _with_dropped(out, _log)


def reset() -> None:
    """Clear spans and counters; what was cleared is the last drained log."""
    global _log, _last
    with _mu:
        log, _log = _log, _Log()
        _last = {"spans": list(log.ring),
                 "counters": _with_dropped(dict(_counters), log)}
        _counters.clear()


def drained() -> Dict[str, object]:
    """{"spans": [Span], "counters": {name: n}} of the last `reset()`."""
    with _mu:
        return _last


# -- what the readers of a log share ------------------------------------------

Interval = Tuple[int, int]


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        elif e > s:
            merged.append((s, e))
    return merged


def covered_s(log: Iterable[Span], within: Iterable[Interval]) -> float:
    """Seconds of the union of the spans' intervals that lie inside the
    (disjoint) intervals `within`."""
    merged = _union((s.start_ns, s.end_ns) for s in log)
    total = 0
    for lo, hi in within:
        total += sum(min(e, hi) - max(s, lo) for s, e in merged
                     if e > lo and s < hi)
    return total / 1e9


def by_name(log: Iterable[Span]) -> Dict[str, Tuple[int, float, float]]:
    """{name: (count, total seconds, self seconds)}; self time is a span's
    duration less the part of it that its children cover."""
    log = list(log)
    children: Dict[int, List[Span]] = {}
    for s in log:
        if s.parent:
            children.setdefault(s.parent, []).append(s)
    out: Dict[str, Tuple[int, float, float]] = {}
    for s in log:
        inside = covered_s(children.get(s.id, ()), [(s.start_ns, s.end_ns)])
        n, total, own = out.get(s.name, (0, 0.0, 0.0))
        out[s.name] = (n + 1, total + s.seconds, own + s.seconds - inside)
    return out


def leaves(log: Iterable[Span]) -> List[Span]:
    """The spans of `log` that are no span's parent."""
    log = list(log)
    parents = {s.parent for s in log}
    return [s for s in log if s.id not in parents]


def timeline(job: str, log: Optional[Iterable[Span]] = None) -> str:
    """The spans of one request, ordered by start and indented by parent,
    with milliseconds from the request's first span: where one query went."""
    mine = sorted((s for s in (spans() if log is None else log) if s.job == job),
                  key=lambda s: (s.start_ns, s.id))
    if not mine:
        return f"no span of job {job!r}"
    by_id = {s.id: s for s in mine}
    t0 = mine[0].start_ns

    def depth(s: Span) -> int:
        d = 0
        while s.parent in by_id:
            s, d = by_id[s.parent], d + 1
        return d

    lines = []
    for s in mine:
        ids = "".join(f" {k}={v}" for k, v in (("stage", s.stage), ("partition", s.partition))
                      if v is not None)
        attrs = "".join(f" {k}={v}" for k, v in s.attrs.items())
        lines.append(f"{(s.start_ns - t0) / 1e6:9.3f} ms {'  ' * depth(s)}{s.name} "
                     f"{s.seconds * 1e3:.3f} ms{ids}{attrs} [t{s.tid % 10000}]")
    return "\n".join(lines)
