"""What the benchmark reads from the program besides its outputs: the
runtime's tier counters and JAX's compile events."""

from __future__ import annotations

import threading
from typing import Any, Dict


def tier_report(rts) -> Dict[str, Any]:
    """What a runtime executed: its fusion counters, its carriers per tier
    and the breakers that opened."""
    from repro.core.policies import BREAKER_TRANSITIONS
    from repro.rts.jax_rts import CARRIERS_TOTAL

    carriers = {labels["tier"]: c.value for labels, c in
                rts.metrics.collect("counter", CARRIERS_TOTAL)}
    opened = sum(c.value for labels, c in rts.metrics.collect(
        "counter", BREAKER_TRANSITIONS) if labels.get("to") == "open")
    return {"fusion_stats": rts.fusion_stats, "carriers": carriers,
            "breakers_opened": opened}


def tier_faults(tiers: Dict[str, Any], tier: str, carriers: int) -> int:
    """Degraded carriers, scalar fallbacks, opened breakers, and carriers
    missing on the intended tier: 0 for a campaign that ran as planned."""
    stats = tiers["fusion_stats"]
    short = max(0, carriers - tiers["carriers"].get(tier, 0))
    return (stats["degraded"] + stats["scalar_fallback"]
            + tiers["breakers_opened"] + short)


class CompileMeter:
    """Counts XLA compiles while open, from JAX's monitoring events (the
    runtime's threads compile too). A program loaded from the persistent
    cache counts as a compile of its load time."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            with self._lock:
                self.compiles += 1
                self.seconds += duration

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
