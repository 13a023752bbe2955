"""Operations and bytes of the blocked top-K Pearson scan, from the
algorithm's shapes alone, as ``roofline.py`` counts the other kernels:
tiles, panels and precision passes never enter the count."""

from __future__ import annotations

from typing import Tuple

import roofline

I32 = 4


def topk_counts(n: int, L: int, K: int) -> Tuple[float, float]:
    """(ops, bytes) of the top-K table of n series of length L: the
    n x n x L multiply-adds of the similarity it scans, the standardized
    series read once, and K float32 values and K int32 indices written a
    row."""
    return (2.0 * n * n * L,
            float(n * L * roofline.F32 + n * K * (roofline.F32 + I32)))
