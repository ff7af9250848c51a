"""The comparison that decides `correct`: the tables the client received in
the window against the plain reference's answer on the same generated data.

Three numbers per cell, each with a limit of its own (the configuration's
file states them under `limits`):

  answers_missing   queries that failed or returned no table
  exact_mismatches  answers whose shape, column names, or any value of a
                    column that is not a float (keys, counts, strings, dates,
                    row order) differ from the reference
  rel_err_max       the largest |got - want| / |want| over every float value
                    of every answer (the device accumulates in float32, the
                    reference in float64)
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import pandas as pd
import pyarrow as pa

NUMBERS = ("answers_missing", "exact_mismatches", "rel_err_max")


def to_frame(table: pa.Table) -> pd.DataFrame:
    """The client's Arrow table as the reference returns its answers: dates
    as days since 1970-01-01, everything else as pandas gives it."""
    for i, f in enumerate(table.schema):
        if pa.types.is_date(f.type):
            table = table.set_column(
                i, f.name, table.column(i).cast(pa.date32()).cast(pa.int32()))
    return table.to_pandas()


def compare_answer(got: pd.DataFrame, want: pd.DataFrame,
                   sort_by: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """{"exact_mismatch": 0 or 1, "rel_err": worst float error, "why": str}.
    `sort_by` is given for a text without ORDER BY: both sides are sorted by
    those key columns first."""
    if list(got.columns) != list(want.columns):
        return {"exact_mismatch": 1, "rel_err": 0.0,
                "why": f"columns {list(got.columns)} != {list(want.columns)}"}
    if len(got) != len(want):
        return {"exact_mismatch": 1, "rel_err": 0.0,
                "why": f"{len(got)} rows != {len(want)}"}
    if sort_by:
        got = got.sort_values(list(sort_by)).reset_index(drop=True)
        want = want.sort_values(list(sort_by)).reset_index(drop=True)
    worst, mismatch, why = 0.0, 0, ""
    for c in want.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if w.dtype.kind != "f":
            if list(g) != list(w) and not mismatch:
                mismatch, why = 1, f"column {c}: {list(g[:4])} != {list(w[:4])}"
            continue
        g, w = g.astype(np.float64), w.astype(np.float64)
        if not np.array_equal(np.isnan(g), np.isnan(w)):
            if not mismatch:
                mismatch, why = 1, f"column {c}: NULLs differ"
            continue
        ok = ~np.isnan(w)
        if not ok.any():
            continue
        # a reference value of exactly 0 admits only 0
        err = np.abs(g[ok] - w[ok]) / np.where(w[ok] == 0, 1e-300, np.abs(w[ok]))
        e = float(err.max())
        if e > worst:
            worst = e
            why = why or f"column {c}"
    return {"exact_mismatch": mismatch, "rel_err": worst, "why": why}


def compare_window(answers: List[dict], wants: Dict[str, pd.DataFrame],
                   sort_by: Dict[str, Sequence[str]], failed: int,
                   limits: Dict[str, float]) -> Dict[str, object]:
    """Every answer of the window against the reference. `answers` are
    {"text": name, "table": pa.Table}; an answer bit-equal to one of the same
    text already judged takes that verdict. Returns {"correct": bool,
    "compared": {number: {"value", "limit"}}, "per_text": {...}, "notes"}."""
    judged: Dict[str, List[tuple]] = {}
    per_text: Dict[str, Dict[str, float]] = {}
    notes: List[str] = []
    mismatches, worst = 0, 0.0
    for a in answers:
        name, table = a["text"], a["table"]
        verdict = next((v for t, v in judged.get(name, []) if t.equals(table)), None)
        if verdict is None:
            verdict = compare_answer(to_frame(table), wants[name], sort_by.get(name))
            judged.setdefault(name, []).append((table, verdict))
            if verdict["exact_mismatch"]:
                notes.append(f"{name}: {verdict['why']}")
        s = per_text.setdefault(name, {"answers": 0, "exact_mismatches": 0,
                                       "rel_err_max": 0.0})
        s["answers"] += 1
        s["exact_mismatches"] += verdict["exact_mismatch"]
        s["rel_err_max"] = max(s["rel_err_max"], verdict["rel_err"])
        mismatches += verdict["exact_mismatch"]
        worst = max(worst, verdict["rel_err"])
    values = {"answers_missing": failed, "exact_mismatches": mismatches,
              "rel_err_max": worst}
    compared = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS}
    correct = bool(answers) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return {"correct": correct, "compared": compared, "per_text": per_text,
            "notes": notes}
