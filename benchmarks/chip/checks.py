"""One number compared, beside its limit."""

from __future__ import annotations


def check(name: str, value: float, limit: float) -> dict:
    """``value <= limit`` passes; a value that is not a number fails."""
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(value <= limit)}


def gap_sums(served, reference):
    """(widest ``|served - reference|``, sum of its squares) in float64: the
    widest catches one altered answer; the mean square is steady from seed
    to seed and separates a lower precision."""
    import numpy as np
    d = np.abs(np.asarray(served, np.float64) - reference)
    return float(d.max()), float(np.sum(d * d))
