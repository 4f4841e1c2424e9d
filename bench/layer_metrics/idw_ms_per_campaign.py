"""Member programs (the AnEn IDW): device time of the jitted
``idw_interpolate`` programs per campaign, in ms."""

from bench.layers import op_ns


def read(window):
    ns = op_ns(window, lambda name: "idw_interpolate" in name, modules=True)
    if ns is None or not window.campaigns:
        return None
    return ns / 1e6 / window.campaigns
