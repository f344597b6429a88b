"""The control's arithmetic: values rounded to the precision below the one a configuration states."""

from __future__ import annotations

import numpy as np


def to_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> nearest bfloat16 (ties to even), returned as float64."""
    bits = np.asarray(values, np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32).astype(np.float64)


def lower(values, precision: str | None):
    """``values`` (a pandas Series or array of float64) rounded to ``precision``; None leaves them."""
    if precision is None:
        return values
    if precision == "float32":
        return values.astype(np.float32).astype(np.float64)
    if precision == "bfloat16":
        out = to_bfloat16(np.asarray(values, np.float64))
        return type(values)(out, index=values.index) if hasattr(values, "index") else out
    raise ValueError(f"unknown control precision {precision!r}")


#: lowest first: an answer from a lane that stands before the stated precision here is a departure
RANK = {"bfloat16": 0, "float32": 1, "float64": 2}
#: the nearest precision below the one a query's configuration states
BELOW = {"float64": "float32", "float32": "bfloat16"}
