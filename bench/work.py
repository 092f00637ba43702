"""Work counts: the operations and bytes a product needs, from its shapes.

Two counts, both independent of what implements the product:

* block level (what the chip executes): every block product is a dense
  ``bs x bs x bs`` GEMM, ``2 bs^3`` flops; the compulsory bytes are each
  block of A, B and C crossing HBM once;
* element level (the paper's Table 1): ``sum_k col_nnz(k) row_nnz(k)``
  multiply-adds over the element band, the count the paper reports
  (7.022 TFLOP for the banded family at n = 100,000).
"""

from __future__ import annotations

import numpy as np

from .structure import product_tasks

BANDW = 3000  # paper: element band half-width 3000 (bandwidth 2*3000 + 1)


def block_product_work(a: np.ndarray, b: np.ndarray, c_nnzb: int, bs: int,
                       itemsize: int) -> dict:
    """Flops and compulsory HBM bytes of C = A·B at block granularity."""
    tasks = product_tasks(a, b)
    return dict(tasks=tasks, flops=2.0 * tasks * bs**3,
                bytes=float(len(a) + len(b) + c_nnzb) * bs * bs * itemsize)


def _band_counts(n: int, h: int) -> np.ndarray:
    k = np.arange(n, dtype=np.int64)
    return np.minimum(n - 1, k + h) - np.maximum(0, k - h) + 1


def banded_element_flops(n: int, h: int = BANDW) -> float:
    """Table 1's count for A·A with A banded of half-width ``h``: A is
    symmetric in structure, so column and row counts of k agree."""
    c = _band_counts(n, h).astype(np.float64)
    return float(2.0 * np.sum(c * c))
