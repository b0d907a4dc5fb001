"""Builders of the host matrices the configurations name.

``problems/<problem>.py`` exposes ``build(config, chips, overrides)`` and
returns a :class:`Problem`. The matrix is data handed to the program, made
here and not by the program, so the reference can use it as it is.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Problem:
    a: object  # scipy.sparse.csr_matrix, float64
    chips: int
    dtype: str  # the working dtype of the solve, as the configuration says

    @property
    def n(self) -> int:
        return int(self.a.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.a.nnz)

    @property
    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize

    def row_blocks(self) -> list[tuple[int, int]]:
        """``(rows, nnz)`` each chip owns in a row-block layout of equal
        contiguous blocks, the paper's distribution."""
        cuts = np.linspace(0, self.n, self.chips + 1).astype(np.int64)
        ptr = self.a.indptr
        return [(int(hi - lo), int(ptr[hi] - ptr[lo]))
                for lo, hi in zip(cuts[:-1], cuts[1:])]
