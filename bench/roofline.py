"""Operations and bytes of the kernels, from the algorithm's shapes alone,
and the table of peaks they are held against.

The counts never depend on tiles, padding or precision passes, so a
kernel's share of its roofline reads the same work whatever implements
it: ``least time = max(ops / peak ops, bytes / peak bytes)``.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
HUB_MIN_N = 200          # below this the program's hub APSP is exact


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def pearson_counts(n: int, L: int) -> Tuple[float, float]:
    """(ops, bytes) of one (n, L) -> (n, n) Pearson matrix: the n x n x L
    multiply-adds, the series read once and the matrix written once."""
    return 2.0 * n * n * L, float(n * L * F32 + n * n * F32)


def minplus_bytes(m: int, k: int, n: int) -> float:
    """Compulsory bytes of an (m, k) x (k, n) min-plus product: both
    operands read once, the result written once."""
    return float((m * k + k * n + m * n) * F32)


def hub_count(n: int) -> int:
    return min(n, max(4, math.ceil(math.sqrt(n))))


def apsp_product_bytes(n: int) -> float:
    """Bytes of one min-plus product of the APSP stage at n vertices.

    The hub APSP relaxes (h, n) x (n, n) per Bellman-Ford round and
    composes (n, h) x (h, n) once: both move h*n + h*n + n*n floats.
    Below ``HUB_MIN_N`` the stage squares (n, n) x (n, n)."""
    if n < HUB_MIN_N:
        return minplus_bytes(n, n, n)
    h = hub_count(n)
    return minplus_bytes(h, n, n)


def least_seconds(ops: float, nbytes: float, peak: dict,
                  bytes_only: bool = False) -> float:
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    if bytes_only:
        return t_bytes
    return max(ops / peak["bf16_flops_per_s"], t_bytes)
