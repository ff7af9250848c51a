"""Device join kernel: sort + per-probe run look-up with M:N multiplicity.

The TPU-native lowering of the hash join (every TPC-H join, primary-key or
not): build-side key codes are sorted on device ONCE (stable, so equal keys
keep build-row order) and each probe key finds its run in the sorted plane,
a start and a run-length, by one of two look-ups chosen from the key range
the join observes (ISSUE 34):

- **position table** (table_runs, program `join_runs_table`) where the codes
  are dense: a scatter-add of the build codes and a prefix sum give, per
  code, the run's start and length, and a probe reads both by its code. It
  answers when the range, bucketed, is at most _TABLE_MAX_ENTRIES and
  neither it nor the build plane exceeds _TABLE_SLOTS_PER_PROBE_SLOT slots a
  probe slot; a single integer key is `value - lo` (physical/joinutil.py),
  so every TPC-H key is dense.
- **paired binary search** (match_runs, program `join_runs`) elsewhere: a
  packed composite key whose range is wide, a long range or a long build
  under a short probe. jnp.searchsorted side='left'/'right' lowers to a loop of dependent
  gathers over the whole probe plane, one a halving: a v5e's trace read
  0.21 s a million probes for the pair (2 x 14 gathers of 7.5 ms over 8k
  build rows), 97 % of the device's time in the cell that joins most.

Both return the same starts and counts bit for bit wherever a probe has a
match. Duplicate build keys do not decline: run-lengths exclusive-scan into
per-probe output offsets on the host flatten, and matches materialize
through a bounded-width gather whose static width is the smallest admission
tier (ops/kernels.py::JOIN_MULTIPLICITY_TIERS) covering the observed maximum
multiplicity, keeping every program shape static.

Adaptive execution (ISSUE 10) replaces the wholesale decline past the
static ladder with three measured-cost escapes, every one bit-identical to
the host oracle:

- **extended tiers** — with a warm cost store whose evidence says the
  device gather beats the host join (kernels.join_extended_tier), widths
  512/1024 admit under hard caps; a gross mispredict re-tiers the store so
  the next decision falls back.
- **partial offload** — a batch past a tier boundary SPLITS at the
  boundary: probes whose run-length fits the boundary tier gather on
  device, the few dominant (skewed) keys past it join on the host oracle,
  and the two selections merge probe-major — bit-identical to the
  wholesale host join by construction, asserted against the oracle's own
  run-lengths before merging.
- **cold paths unchanged** — no config / cost model off / no structural
  skew reproduces the pre-adaptive step-aside exactly.

The compiled programs (the two runs kernels and each gather width) ride the
persistent AOT disk tier (ops/aotcache.py) under a stable plan-independent
key, so a cold process reloads them as compile_hit_disk instead of fresh
traces (ISSUE 10 satellite; PR 8 residue).

Shapes past every escape step aside to the host sort-merge join
(physical/joinutil.py) with a recorded reason; both paths share the same
key normalization and emit matches in the same order — probe-major, build
rows in stable sorted order within a probe key — so device results are
bit-identical to the host oracle, multiplicity and order included.

Every decline flows through the canonical kernels helpers AND
runtime.record_join_path, so the benchmark's `join_paths` (device / split /
step_aside / host_fallback, with reasons; benchmarks/chip/run.py::
drain_counters reads join_path_stats) stay truthful;
every engine choice additionally lands in the routing accumulator
(runtime.record_routing) with its predicted-vs-observed cost.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import numpy as np
import pyarrow as pa

from ballista_tpu.ops.runtime import (
    bucket_rows,
    pad_to,
    readback,
    record_join_path,
    record_routing,
    record_routing_event,
    routing_probe,
)
from ballista_tpu.utils import tracing

_PAD_CODE = np.int32(2**31 - 1)  # sorts last, never matches a valid probe

# partial offload engages only for the skew shape it is built for: at most
# this many DISTINCT keys past the tier boundary go to the host remainder
# (a broadly-duplicated build is not a split candidate — host-wholesale or
# an evidence-backed extended tier handles it)
_SPLIT_MAX_HOT_KEYS = 16
# planned-build-side row excess past which the observed cardinalities are
# treated as a plan-time misestimate and the build side switches
_BUILD_SWAP_RATIO = 4
# a probe finds its run in a position table (table_runs) where the table is
# at most this long (two int32 planes of 128 MB, 0.67 GB of temporaries) and
# neither the table nor the build plane is longer than this multiple of the
# probe slots: building it (0.29 ns an entry, 7.8 ns a build row on a v5e)
# then costs a fraction of the searches it replaces (200-270 ns a probe
# slot). Wider ranges and longer builds keep the paired search (match_runs)
_TABLE_MAX_ENTRIES = 1 << 25
_TABLE_SLOTS_PER_PROBE_SLOT = 64


class _JoinProgramOwner:
    """AOT-cache identity for the device-join programs. They are pure
    shape functions — no plan structure, no literals — so one stable key
    serves every join and a cold executor reloads them from disk
    (compile_hit_disk) instead of retracing."""

    aot_key = "ops.join"


_AOT_OWNER = _JoinProgramOwner()


def _join_span(build_rows: int, probe_rows: int, **attrs) -> tracing.Span:
    """`runtime.join`: a device join's work on one partition, one span
    around the encoding of its keys and one around a probe batch (sort,
    search, gather, their readbacks and the flattening into selections are
    its children). It ends with the `path` the batch took and its `out_rows`."""
    return tracing.span("runtime.join", build_rows=build_rows,
                        probe_rows=probe_rows, **attrs)


def _set_on_join_span(**attrs) -> None:
    """Attributes of the `runtime.join` span a probe batch is decided in."""
    sp = tracing.current()
    if sp is not None and sp.name == "runtime.join":
        sp.set(**attrs)


def _record_path(path: str, reason: Optional[str] = None) -> None:
    """The path a join took: counted for join_path_stats, and named on the
    `runtime.join` span it is decided in."""
    record_join_path(path, reason)
    _set_on_join_span(path=path)


def match_runs(sorted_codes, probe_codes):
    """Per-probe match run over a sorted build-code plane (traced):
    paired searchsorted left/right -> (starts, counts), both int32. Null
    probe codes (-1) and probe pad slots yield count 0; null build codes
    sort below every valid probe code and build pad codes above, so
    [starts, ends) never spans either. ONE source of truth shared by the
    single-chip kernel below and the SPMD mesh program (spmd_join.py) —
    the two device join paths must never drift."""
    import jax.numpy as jnp

    starts = jnp.searchsorted(sorted_codes, probe_codes, side="left")
    ends = jnp.searchsorted(sorted_codes, probe_codes, side="right")
    counts = jnp.where(probe_codes >= 0, ends - starts, 0)
    return starts.astype(jnp.int32), counts.astype(jnp.int32)


def _prefix_sum(x):
    """Inclusive prefix sum of a power-of-two-long int32 vector (traced), in
    two levels: rows of 2,048, then the rows' totals. One jnp.cumsum over a
    2M-entry table took the chip's compiler 8.5-13.4 s a program shape, this
    form 0.7 s (PERF.md, PR 34)."""
    import jax.numpy as jnp

    rows = jnp.cumsum(x.reshape(-1, min(x.shape[0], 2048)), axis=1, dtype=jnp.int32)
    before = jnp.cumsum(rows[:, -1], dtype=jnp.int32) - rows[:, -1]
    return (rows + before[:, None]).reshape(-1)


def table_runs(build_codes, probe_codes, entries: int):
    """match_runs' answer read off a position table (traced), for codes in
    [0, entries), a power of two: per code c, how many build rows carry it
    (a scatter-add; null codes -1 and `_PAD_CODE` fall outside and are
    dropped) and where its run begins in the stable-sorted build plane, the
    null build rows plus the exclusive prefix sum of the counts (nulls sort
    first, pads last): what searchsorted(side="left") returns there. The two
    are one table of two-wide rows, so a probe is ONE gather (6.1 ms a
    million probes on a v5e; two gathers of two tables 24.4). Bit-identical
    to match_runs in `counts` everywhere and in `starts` wherever `counts`
    is above 0; a probe without a match gets some other start, which nothing
    reads (gather_matches masks past the run length)."""
    import jax.numpy as jnp

    valid = (build_codes >= 0) & (build_codes < entries)
    counts_t = jnp.zeros(entries, jnp.int32).at[
        jnp.where(valid, build_codes, entries)
    ].add(1, mode="drop")
    nulls = jnp.sum(build_codes < 0, dtype=jnp.int32)
    starts_t = nulls + _prefix_sum(counts_t) - counts_t
    row = jnp.stack([starts_t, counts_t], axis=1)[jnp.clip(probe_codes, 0, entries - 1)]
    inside = (probe_codes >= 0) & (probe_codes < entries)
    return row[:, 0], jnp.where(inside, row[:, 1], 0)


def gather_matches(values, starts, counts, width: int):
    """Bounded-width gather (traced): [P, width] of values[starts + j],
    masked to -1 past each probe's run length. Shared with the mesh
    program, like match_runs."""
    import jax.numpy as jnp

    n = values.shape[0]
    j = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.clip(starts[:, None] + j[None, :], 0, n - 1)
    return jnp.where(j[None, :] < counts[:, None], values[idx], -1)


@functools.lru_cache(maxsize=None)
def _runs_kernel(table: bool = False):
    """The runs program, one of two by how a probe finds its run:
    `join_runs` searches the sorted plane, `join_runs_table` reads a position
    table of a static number of entries (its leading argument)."""
    from ballista_tpu.ops import aotcache

    def _order(build_codes):
        import jax.numpy as jnp

        # stable: equal build keys keep original row order, matching the
        # host oracle's kind="stable" argsort (bit-equal output order)
        return jnp.argsort(build_codes, stable=True)

    def runs(build_codes, probe_codes):
        order = _order(build_codes)
        return (order, *match_runs(build_codes[order], probe_codes))

    def runs_table(entries, build_codes, probe_codes):
        return (_order(build_codes), *table_runs(build_codes, probe_codes, entries))

    if table:
        return aotcache.wrap_step(
            _AOT_OWNER, "join_runs_table", runs_table, static_argnums=(0,)
        )
    return aotcache.wrap_step(_AOT_OWNER, "join_runs", runs, static_argnums=())


@functools.lru_cache(maxsize=None)
def _gather_kernel(width: int):
    from ballista_tpu.ops import aotcache

    def gather(order, starts, counts):
        return gather_matches(order, starts, counts, width)

    # width is baked into the closure, not an argument: the program name
    # carries it so each width keys its own AOT artifact
    return aotcache.wrap_step(
        _AOT_OWNER, f"join_gather_w{width}", gather, static_argnums=()
    )


def _decline(kind: str, reason: str) -> None:
    """Join decline: record the path for join_path_stats
    (`kind` distinguishes admission-tier "step_aside" declines from other
    "host_fallback" declines), then route through the canonical
    host_fallback helper — either way the join leaves the device entirely,
    so tracing must count a fallback, not a mid-ladder step-aside."""
    from ballista_tpu.ops.kernels import host_fallback

    _record_path(kind, reason)
    record_routing("host", "join")
    return host_fallback(reason)


def _counts_plane(build_codes: np.ndarray, probe_codes: np.ndarray):
    """Shared admission + padding + run-length pass for BOTH device join
    entries: (order, starts, counts [device], counts_h [host, unpadded],
    n_probe), or None after a recorded decline (empty side, code range
    past int32). One implementation so the full-join and counts-only
    planes can never diverge on sentinels, bucketing, or admission."""
    import jax.numpy as jnp

    nb, np_ = len(build_codes), len(probe_codes)
    if nb == 0 or np_ == 0:
        return _decline("host_fallback", "empty join side")
    hi = max(int(build_codes.max()), int(probe_codes.max()))
    if hi >= 2**31 - 2:
        return _decline("host_fallback", "join key codes exceed int32")
    b = jnp.asarray(
        pad_to(build_codes.astype(np.int32), bucket_rows(nb, 16), _PAD_CODE)
    )
    # null probe keys (-1) search below all valid codes and lie outside the
    # position table — already a non-match; pads reuse the same sentinel
    p = jnp.asarray(pad_to(probe_codes.astype(np.int32), bucket_rows(np_, 16), -1))
    # the look-up adapts to the key range observed: a dense range reads a
    # position table, a wide one (a packed composite key) keeps the search
    entries = bucket_rows(hi + 1)
    if entries <= _TABLE_MAX_ENTRIES and (
        max(entries, b.shape[0]) <= _TABLE_SLOTS_PER_PROBE_SLOT * p.shape[0]
    ):
        method = "table"
        order, starts, counts = _runs_kernel(table=True)(entries, b, p)
    else:
        method = "search"
        order, starts, counts = _runs_kernel()(b, p)
    tracing.incr(f"device.join_{method}_probes", np_)
    _set_on_join_span(method=method, entries=entries)
    counts_h = readback(counts)[:np_]
    return order, starts, counts, counts_h, np_


def _run_gather(order, starts, counts, tier: int, np_: int) -> Tuple[np.ndarray, float]:
    """Execute the bounded-width gather at `tier` and feed the cost store:
    (matched-plane [np_, tier], observed seconds)."""
    from ballista_tpu.ops import costmodel

    t0 = time.perf_counter()
    mat = readback(_gather_kernel(tier)(order, starts, counts), rows=np_)[:np_]
    dt = time.perf_counter() - t0
    costmodel.observe("join.gather", int(counts.shape[0]) * tier, dt)
    return mat, dt


def _flatten_matched(mat: np.ndarray, counts_h: np.ndarray, np_: int):
    """Host flatten of the gathered match plane: probe-major (build, probe)
    selections — the run-length exclusive scan is implicit in the
    row-major compaction (probe-major, slot order within each probe)."""
    tier = mat.shape[1]
    keep = np.arange(tier, dtype=np.int32)[None, :] < counts_h[:, None]
    build_idx = mat[keep].astype(np.int64)
    probe_idx = np.repeat(np.arange(np_, dtype=np.int64), counts_h)
    return build_idx, probe_idx


def _within_runs(counts: np.ndarray) -> np.ndarray:
    """[0..c) position index for each run of a counts vector, flattened."""
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    cum = np.cumsum(counts, dtype=np.int64)
    return np.arange(total, dtype=np.int64) - np.repeat(cum - counts, counts)


def _split_offload(
    order, starts, counts, counts_h, np_,
    build_codes: np.ndarray, probe_codes: np.ndarray,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Partial offload (ISSUE 10): split the batch at the tier boundary
    instead of declining it wholesale. Probes whose run-length fits the
    largest cap-admissible tier gather on device; the dominant keys past it
    (at most _SPLIT_MAX_HOT_KEYS distinct — the skew shape) join on the
    host oracle; selections merge probe-major. Bit-identity with the
    wholesale host join holds by construction — both sides emit build rows
    in stable sorted order within a probe key — and the host remainder's
    run-lengths are asserted against the device counts plane before the
    merge. Returns None when the shape is not a split candidate."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.ops.kernels import (
        JOIN_GATHER_CAP,
        JOIN_MULTIPLICITY_TIERS,
        join_multiplicity_tier,
    )
    from ballista_tpu.physical.joinutil import join_indices

    probe_slots = int(counts.shape[0])
    boundary = JOIN_MULTIPLICITY_TIERS[0]
    for t in JOIN_MULTIPLICITY_TIERS:
        if t == 1 or probe_slots * t <= JOIN_GATHER_CAP:
            boundary = t
    hot = counts_h > boundary
    if not hot.any():
        return None  # nothing past the boundary: not this escape's shape
    if len(np.unique(probe_codes[hot])) > _SPLIT_MAX_HOT_KEYS:
        return None  # broad duplication, not skew — splitting buys nothing
    cold = ~hot
    cold_max = int(counts_h[cold].max()) if cold.any() else 0
    cold_tier, _why = join_multiplicity_tier(cold_max, probe_slots)
    if cold_tier is None or cold_tier > boundary:
        return None
    # input-row units, like every other join.host site (the op-global rate
    # is shared; match-count units would dilute it and skew the extended-
    # tier gate's host predictions)
    host_units = len(build_codes) + int(hot.sum())
    predicted = None
    dev_pred = costmodel.predict("join.gather", probe_slots * cold_tier)
    host_pred = costmodel.predict("join.host", host_units, engine="host")
    if dev_pred is not None and host_pred is not None:
        predicted = dev_pred + host_pred

    mat, dt_dev = _run_gather(order, starts, counts, cold_tier, np_)
    # host remainder: the oracle on the hot probes only
    hot_sel = np.flatnonzero(hot)
    t_host = time.perf_counter()
    bi_hot, pi_hot = join_indices(build_codes, probe_codes[hot_sel], "inner")
    dt_host = time.perf_counter() - t_host
    costmodel.observe("join.host", host_units, dt_host, engine="host")
    # per-op re-tiering on gross mispredicts (either direction): without
    # it a first-call trace/compile outlier inflates the gather rate for
    # _FORGET_AT observations and the composite prediction stays wrong
    costmodel.check_mispredict(
        "join.gather", probe_slots * cold_tier, dev_pred, dt_dev
    )
    costmodel.check_mispredict(
        "join.host", host_units, host_pred, dt_host, engine="host"
    )
    # decision-point oracle assertion: the host remainder's run-lengths
    # must equal the device counts plane for those probes — a mismatch
    # means the two engines disagree about the data and the split must not
    # merge (fall back to the wholesale host join instead)
    hot_counts = counts_h[hot_sel].astype(np.int64)
    if len(bi_hot) != int(hot_counts.sum()) or not np.array_equal(
        np.bincount(pi_hot, minlength=len(hot_sel)), hot_counts
    ):
        record_routing_event("split_oracle_mismatch")
        return None

    offsets = np.concatenate(
        ([0], np.cumsum(counts_h, dtype=np.int64)[:-1])
    )
    total = int(counts_h.sum())
    build_idx = np.empty(total, dtype=np.int64)
    cold_sel = np.flatnonzero(cold)
    cold_counts = counts_h[cold_sel].astype(np.int64)
    keep_cold = (
        np.arange(cold_tier, dtype=np.int32)[None, :] < counts_h[:, None]
    ) & cold[:, None]
    build_idx[
        np.repeat(offsets[cold_sel], cold_counts) + _within_runs(cold_counts)
    ] = mat[keep_cold].astype(np.int64)
    build_idx[
        np.repeat(offsets[hot_sel], hot_counts) + _within_runs(hot_counts)
    ] = bi_hot
    probe_idx = np.repeat(np.arange(np_, dtype=np.int64), counts_h)
    _record_path("split", "partial offload at the tier boundary")
    # observed = the modeled work (gather + host join); the merge scatter
    # and oracle assertion are not part of the prediction, so timing them
    # would bill measurement scope as model error in the mispredict rate
    record_routing("split", "join", predicted, dt_dev + dt_host)
    record_routing_event("split")
    return build_idx, probe_idx, counts_h.astype(np.int64)


def _extended_gather(
    order, starts, counts, counts_h, np_,
    max_mult: int, host_units: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Evidence-gated gather at an extended tier (past the static ladder).
    A gross mispredict re-tiers the cost store so the next decision for
    this shape bucket falls back to the static prior."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.ops.kernels import join_extended_tier

    probe_slots = int(counts.shape[0])
    ext = join_extended_tier(max_mult, probe_slots, host_units)
    if ext is None:
        return None
    tier, dev_pred, _host_pred = ext
    mat, dt = _run_gather(order, starts, counts, tier, np_)
    record_routing("device", "join.extended", dev_pred, dt)
    costmodel.check_mispredict("join.gather", probe_slots * tier, dev_pred, dt)
    build_idx, probe_idx = _flatten_matched(mat, counts_h, np_)
    _record_path("device", "extended tier past the static ladder")
    return build_idx, probe_idx, counts_h.astype(np.int64)


def device_join_indices(
    build_codes: np.ndarray, probe_codes: np.ndarray, config=None
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """M:N inner-join row selections computed on device.

    Returns (build_idx, probe_idx, counts): flat int64 selections realizing
    every (build, probe) key match — probe-major, build rows in stable
    sorted order within a probe key, bit-identical to the host oracle's
    ``join_indices(..., "inner")`` — plus per-probe match run-lengths
    (LEFT-join and membership-count consumers read unmatched probes off
    ``counts == 0``). None when the device path declines (empty side, code
    range too wide for int32, multiplicity past the top admission tier);
    every decline carries a recorded reason.

    With a config whose ``ballista.tpu.cost_model`` is on, shapes the
    static ladder declines first try the measured-cost escapes (extended
    tier, partial-offload split — see the module docstring); without one
    the static ladder is the whole story, so direct callers keep the
    pre-adaptive contract exactly.
    """
    with _join_span(len(build_codes), len(probe_codes)) as sp:
        res = _join_batch(build_codes, probe_codes, config)
        sp.set(out_rows=0 if res is None else len(res[0]))
        return res


def _join_batch(
    build_codes: np.ndarray, probe_codes: np.ndarray, config
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """device_join_indices' work, inside its `runtime.join` span."""
    from ballista_tpu.ops import costmodel
    from ballista_tpu.ops.kernels import join_multiplicity_tier

    plane = _counts_plane(build_codes, probe_codes)
    if plane is None:
        return None  # reason recorded by _counts_plane's decline
    order, starts, counts, counts_h, np_ = plane
    max_mult = int(counts_h.max())
    probe_slots = int(counts.shape[0])
    tier, why = join_multiplicity_tier(max_mult, probe_slots)
    if tier is not None:
        predicted = costmodel.predict("join.gather", probe_slots * tier)
        mat, dt = _run_gather(order, starts, counts, tier, np_)
        with tracing.span("runtime.to_arrow", engine="join"):
            build_idx, probe_idx = _flatten_matched(mat, counts_h, np_)
        _record_path("device")
        record_routing("device", "join", predicted, dt)
        # gross mispredict either way re-tiers the bucket: a first-call
        # trace/compile outlier otherwise inflates the rate for _FORGET_AT
        # observations, steering extended admission and the split decision
        # off steady-state reality
        costmodel.check_mispredict(
            "join.gather", probe_slots * tier, predicted, dt
        )
        return build_idx, probe_idx, counts_h.astype(np.int64)
    if config is not None and config.tpu_cost_model():
        costmodel.configure(config)
        host_units = len(build_codes) + len(probe_codes)
        res = _extended_gather(
            order, starts, counts, counts_h, np_, max_mult, host_units
        )
        if res is None:
            res = _split_offload(
                order, starts, counts, counts_h, np_, build_codes, probe_codes
            )
        if res is not None:
            return res
    return _decline("step_aside", why)


def device_membership_counts(
    build_codes: np.ndarray, probe_codes: np.ndarray
) -> Optional[np.ndarray]:
    """Per-probe match run-lengths (membership counts) computed on device —
    the counts-only entry of device_join_indices (ISSUE 7 satellite: the
    q13/q22 wiring). LEFT-join COUNT aggregates and SEMI/ANTI membership
    need ONLY the counts plane: no gather, so no multiplicity tier applies
    — the readback is the one-int32-per-probe plane, the same cap-exempt
    width-1 transfer the pre-M:N kernel always made. Returns int64 counts
    (null probe codes yield 0, matching SQL never-match semantics and the
    host oracle's ``join_indices`` counts bit-for-bit), or None when the
    device declines (empty side, code range past int32) — every decline
    carries a recorded reason."""
    with _join_span(len(build_codes), len(probe_codes), out_rows=0) as sp:
        plane = _counts_plane(build_codes, probe_codes)
        if plane is None:
            return None  # reason recorded by _counts_plane's decline
        _order, _starts, _counts, counts_h, _np = plane
        _record_path("device")
        record_routing("device", "join.counts")
        sp.set(out_rows=int(np.count_nonzero(counts_h)))  # the probes with a match
        return counts_h.astype(np.int64)


def try_device_inner_join(
    build: pa.Table,
    probe: pa.Table,
    build_keys: list,
    probe_keys: list,
    config=None,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Returns (build_idx, probe_idx) row selections realizing the inner
    join — duplicate build keys expand to their full multiplicity — or None
    if the device path declines.

    Runtime re-planning (ISSUE 10): when the cost model is on and the
    observed row counts say the planner picked the wrong build side (build
    more than _BUILD_SWAP_RATIO times the probe), the sides swap — the
    device sorts the smaller plane — and the canonical probe-major order
    is restored host-side. Within a probe key every matched build row
    carries the SAME key code, so the oracle's "stable sorted build order"
    is simply build-row-ascending; a stable sort of the swapped result by
    probe row reproduces it exactly, keeping bit-identity."""
    from ballista_tpu.physical.joinutil import combined_key_codes

    with _join_span(build.num_rows, probe.num_rows, path="encode"):
        bcodes, pcodes = combined_key_codes(
            [build.column(k) for k in build_keys],
            [probe.column(k) for k in probe_keys],
        )
    if (
        config is not None
        and config.tpu_cost_model()
        and len(bcodes) > _BUILD_SWAP_RATIO * max(1, len(pcodes))
    ):
        # probe scope: the swapped shape may decline (its multiplicity
        # profile differs), in which case the planned-side attempt below
        # records the real decision — without the probe one join would
        # count BOTH the probe's host decline and the planned outcome
        with routing_probe() as rp:
            swapped = device_join_indices(pcodes, bcodes, config)
        if swapped is not None:
            rp.commit()
            record_routing_event("join_build_swapped")
            p_rows, b_rows, _counts = swapped
            perm = np.argsort(p_rows, kind="stable")
            return b_rows[perm], p_rows[perm]
        # fall through to the planned sides before giving up on the device
    res = device_join_indices(bcodes, pcodes, config)
    if res is None:
        return None
    build_idx, probe_idx, _counts = res
    return build_idx, probe_idx
