"""The last line of a run: one function builds it, one validates it, and a
run prints only what passed. PR 22 was refused over a traced line without
`window_s` and `busy_s` and without the cell's per-layer metrics."""

from __future__ import annotations

import json
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _number(x: object) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def build(*, correct: bool, attempted: int, failed: int,
          metrics: Dict[str, Tuple[float, str]], device: Dict[str, object],
          compared: Dict[str, Dict[str, float]],
          breakdown: Optional[Dict[str, list]] = None) -> Dict[str, object]:
    """The result object, keys in the order the contract shows them, the
    numbers compared beside their limits last."""
    line: Dict[str, object] = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": value, "unit": unit}
                    for name, (value, unit) in metrics.items()},
        "device": dict(device),
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def validate(line: Dict[str, object], expected: Sequence[Tuple[str, str]],
             trace: bool) -> List[str]:
    """Every way `line` departs from the contract for a run of a cell whose
    metrics (name, unit) for this `--trace` value are `expected`; empty when
    it may be printed."""
    bad: List[str] = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{key} is not a whole number >= 0: {v!r}")
    if not bad and line["failed"] > line["attempted"]:
        bad.append("failed is larger than attempted")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name, unit in expected:
        if name not in metrics:
            bad.append(f"metric {name!r} of this cell is missing")
    for name, m in metrics.items():
        if not NAME_RE.match(name):
            bad.append(f"metric name {name!r} has a character outside "
                       f"letters, digits, '_', '.', '-' or is over 64 long")
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}: {m!r}")
            continue
        if not _number(m["value"]):
            bad.append(f"metric {name!r} has no finite number: {m['value']!r}")
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            bad.append(f"metric {name!r} has a unit outside 1 to 16 letters, "
                       f"digits, '_', '/', '%', '.', '-': {m['unit']!r}")
    for name, unit in expected:
        if isinstance(metrics.get(name), dict) and metrics[name].get("unit") != unit:
            bad.append(f"metric {name!r} has the unit {metrics[name].get('unit')!r}, "
                       f"BENCHMARK.json says {unit!r}")

    device = line["device"]
    if not isinstance(device, dict):
        return bad + ["device is not an object"]
    for key in ("platform", "kind"):
        if not isinstance(device.get(key), str) or not device.get(key):
            bad.append(f"device.{key} is not a name: {device.get(key)!r}")
    for key, least in (("count", 1), ("memory_peak_bytes", 0)):
        v = device.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < least:
            bad.append(f"device.{key} is not a whole number >= {least}: {v!r}")
    if trace:
        w, b = device.get("window_s"), device.get("busy_s")
        if not _number(w) or w <= 0:
            bad.append(f"device.window_s is not above 0: {w!r}")
        elif not _number(b) or not 0 < b <= w:
            bad.append(f"device.busy_s is not in (0, window_s={w!r}]: {b!r}")

    if "breakdown" in line:
        bd = line["breakdown"]
        if not isinstance(bd, dict) or set(bd) - {"device_ops", "idle_gaps"}:
            bad.append("breakdown has keys other than device_ops and idle_gaps")
        else:
            for key, rows in bd.items():
                if not isinstance(rows, list) or len(rows) > 10 or not all(
                        isinstance(r, list) and len(r) == 2
                        and isinstance(r[0], str) and _number(r[1]) for r in rows):
                    bad.append(f"breakdown.{key} is not at most 10 [name, seconds]")
    try:
        json.dumps(line, allow_nan=False)
    except (TypeError, ValueError) as e:
        bad.append(f"the line is not JSON: {e}")
    return bad


def render(line: Dict[str, object]) -> str:
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
