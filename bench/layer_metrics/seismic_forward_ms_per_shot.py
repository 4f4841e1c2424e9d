"""Member programs (the seismic sweep): device time of the window's sweep
programs, the composed forward → misfit → Σ segments (``jit_seg``
modules), over the shots that the window's ``seismic.sweep`` events
report, in ms per shot."""

from bench.layers import op_ns


def read(window):
    shots = sum(e["attrs"]["shots"] for e in window.spans
                if e.get("name") == "seismic.sweep" and e.get("ph") == "i")
    ns = op_ns(window, lambda name: name.startswith("jit_seg"),
               modules=True)
    if not shots or ns is None:
        return None
    return ns / 1e6 / shots
