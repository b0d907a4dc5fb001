"""The plain reference that decides ``correct``: scipy, float64, the CSR.

A solve is correct when its answer satisfies the system it was given: the
relative residual ``||b - A x|| / ||b||``, recomputed here in IEEE float64
from the very CSR and ``b`` the program was handed, is within the
configuration's limit. Nothing of the program is imported or used.
"""

from __future__ import annotations

import numpy as np


def true_relres(a, x: np.ndarray, b: np.ndarray) -> float:
    """``||b - A x|| / ||b||``; inf for a missing or non-finite answer."""
    x = np.asarray(x, np.float64)
    if x.shape != b.shape or not np.all(np.isfinite(x)):
        return float("inf")
    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))
