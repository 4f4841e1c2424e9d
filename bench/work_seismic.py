"""The least work of the seismic sweep's leapfrog, counted from its shapes.

``seismic_stencil_roofline`` divides the least time these counts allow
(``bench/work.least_seconds``) by the device time of the sweep's programs.
The counts come from the grid, the steps and the shots, never from what
the compiled program moves, so a change that stops materialising the
Laplacian's shifted copies raises the share. Every array is float32.
"""

from __future__ import annotations

from typing import Tuple

F32 = 4

#: flops a grid point and step: the 5-point Laplacian (a multiply and four
#: adds) and the update's products with the coefficient and damping fields
#: and its sums (five more)
FLOPS_PER_POINT = 10


def leapfrog(shots: int, steps: int, points: int) -> Tuple[int, int]:
    """Operations and bytes of ``steps`` leapfrog steps of ``shots`` shots
    on a grid of ``points`` (the padded grid): per shot and step the two
    wavefields read and the next one written; per step the coefficient
    and damping fields read once for the whole batch of shots."""
    flops = FLOPS_PER_POINT * points * steps * shots
    nbytes = F32 * points * steps * (3 * shots + 2)
    return flops, nbytes
