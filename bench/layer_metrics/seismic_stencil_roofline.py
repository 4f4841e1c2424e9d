"""Member programs (the seismic sweep): the least time of the leapfrog's
work in the window's campaigns, each counted from the shots, steps and
padded grid points its ``seismic.sweep`` event reports
(``bench/work_seismic.py``), over the device time of the sweep programs
(``jit_seg`` modules), in %."""

from bench import work, work_seismic
from bench.layers import op_ns, roofline_percent


def read(window):
    if window.peak is None:
        return None
    least = sum(work.least_seconds(*work_seismic.leapfrog(
        e["attrs"]["shots"], e["attrs"]["steps"], e["attrs"]["grid_points"]),
        window.peak) for e in window.spans
        if e.get("name") == "seismic.sweep" and e.get("ph") == "i")
    ns = op_ns(window, lambda name: name.startswith("jit_seg"),
               modules=True)
    return roofline_percent(least, ns)
