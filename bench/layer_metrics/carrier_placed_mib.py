"""Fusion engine: mean bytes a carrier's placement moved onto its devices
(``placed_bytes`` of the program's ``fusion.prepare`` span: host arrays,
and device arrays laid out otherwise, each input counted once), in MiB."""


def read(window):
    placed = [s["attrs"]["placed_bytes"] for s in window.spans
              if s.get("name") == "fusion.prepare" and s.get("ph") == "X"
              and "placed_bytes" in s.get("attrs", {})]
    if not placed:
        return None
    return sum(placed) / len(placed) / 2 ** 20
