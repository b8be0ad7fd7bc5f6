"""Order statistics over every sample, with no binning."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """Exact ``q``-th percentile (0..100) over all values, linear
    between order statistics; None for no values."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))

