"""bfloat16 rounding for the control: the plain reference computed one
precision below the float32 the device accumulates in. numpy only."""

from __future__ import annotations

import numpy as np
import pandas as pd

PRECISIONS = ("f64", "bf16")


def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    bits = np.ascontiguousarray(a, dtype=np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def lower(frame: pd.DataFrame, precision: str) -> pd.DataFrame:
    """`frame` with every float column rounded to `precision`: applied to
    the tables as they are loaded and to the answer as it is returned."""
    if precision == "f64":
        return frame
    if precision != "bf16":
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    out = frame.copy()
    for c in out.columns:
        if out[c].dtype.kind == "f":
            out[c] = to_bf16(out[c].to_numpy())
    return out
