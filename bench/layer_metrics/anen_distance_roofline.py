"""Kernels (``kernels/anen_distance``): the least time that the analog
distances of every location the window's campaigns searched allow
(``bench/work.py``, unpadded H, V, N), over the device time of the programs
that ran the Pallas kernel, in %. The kernel reads its operands from
on-chip memory that the ops before it fill from HBM, so its own op time
leaves out the traffic the roofline counts; the programs that hold it
(the composed AnEn rounds) do not."""

from bench import work
from bench.layers import program_ns, roofline_percent


def read(window):
    if window.peak is None:
        return None
    c = window.config
    locations = window.campaigns * c["max_iters"] * c["per_iter"]
    flops, nbytes = work.anen_distance(c["n_hist"], c["n_vars"], locations)
    ns = program_ns(window, lambda name: name.startswith("anen_distance"))
    return roofline_percent(work.least_seconds(flops, nbytes, window.peak),
                            ns)
