"""Statistics the metric readers share."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), or None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def share_pct(part: float, whole: float) -> Optional[float]:
    """``100 * part / whole``, or None where there is nothing to divide."""
    if whole <= 0 or part < 0:
        return None
    return 100.0 * part / whole
