"""Operations and bytes that the algorithm needs, counted from its shapes.

A roofline share divides the least time these counts allow by a measured
device time. The counts come from the problem's shapes, never from what an
implementation happens to move (XLA's cost analysis counts materialised
temporaries and padding), so a change that removes waste raises the share
instead of shrinking the yardstick with the time. Every array is float32.
"""

from __future__ import annotations

from typing import Dict, Tuple

F32 = 4


def anen_distance(h: int, v: int, n: int) -> Tuple[int, int]:
    """The AnEn similarity ``d2[h, n] = Σ_v (f_hist[h, v, n] − f_now[v, n])²``
    at unpadded sizes: read ``f_hist`` (H·V·N) and ``f_now`` (V·N), write
    ``d2`` (H·N); a subtract, a multiply and an add per term."""
    flops = 3 * v * h * n
    nbytes = F32 * (h * v * n + v * n + h * n)
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peak: Dict[str, float]) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
