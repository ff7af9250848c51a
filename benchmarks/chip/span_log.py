"""What the span readers under layer_metrics/ share: the window's spans, as
the program's recorder kept them (`ballista_tpu.utils.tracing.drained()`:
run.py resets the recorder before the window and at its end, so the last
drained log is the window's), summed by name and divided by the queries the
window completed.

Three cases, told apart by what the program shows:
- the program has no recorder (a checkout from before the spans: the parent
  of the PR that brought them): nothing is traced, and the readers say so,
  0.0 ms of spans in every layer and 100 % untraced. lastline.validate
  refuses a line that leaves a declared metric out, so leaving them out
  would end the parent's traced run without a result;
- the recorder is there and the window holds no `client.collect` span, or
  spans fell out of the ring (`tracing.dropped`): the recorder is broken,
  every reader returns None and the line is refused, which is right;
- else the numbers.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

ROOT_SPAN = "client.collect"
ABSENT = "absent"  # the program has no span recorder

_cache: Tuple[object, object] = (None, None)  # (the drained log, its Window)


class Window:
    """The window's spans, by name and by job."""

    def __init__(self, tracing, spans: list, completed: int) -> None:
        self.tracing = tracing
        self.spans = spans
        self.completed = completed
        self.by_name: Dict[str, Tuple[int, float, float]] = tracing.by_name(spans)
        self.by_job: Dict[str, list] = {}
        for s in spans:
            if s.job is not None:
                self.by_job.setdefault(s.job, []).append(s)

    def ms_per_query(self, seconds: float) -> float:
        return 1e3 * seconds / self.completed


def window(run: dict):
    """The Window of this run, ABSENT, or None (nothing to read, or a
    recorder that lost spans)."""
    global _cache
    completed = run["window"]["completed"]
    if not completed:
        return None
    from ballista_tpu.utils import tracing

    if not hasattr(tracing, "drained"):
        return ABSENT
    log = tracing.drained()
    if _cache[0] is not log:
        spans = log["spans"]
        sound = (not log["counters"].get("tracing.dropped")
                 and any(s.name == ROOT_SPAN for s in spans))
        _cache = (log, Window(tracing, spans, completed) if sound else None)
    return _cache[1]


def span_ms(run: dict, total: Sequence[str] = (), own: Sequence[str] = ()) -> Optional[float]:
    """ms per completed query: the whole time of the spans named in `total`
    plus the self time (less what their children cover) of those in `own`.
    A layer that did no work in the cell reads 0.0."""
    w = window(run)
    if w is None:
        return None
    if w is ABSENT:
        return 0.0
    seconds = sum(w.by_name.get(n, (0, 0.0, 0.0))[1] for n in total)
    seconds += sum(w.by_name.get(n, (0, 0.0, 0.0))[2] for n in own)
    return w.ms_per_query(seconds)


def roots(w: Window) -> List[object]:
    return [s for s in w.spans if s.name == ROOT_SPAN and s.job is not None]
