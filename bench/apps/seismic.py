"""Seismic misfit campaigns (paper §III-A) driven through the program's
entry points.

Each campaign is one ``run_sweep`` call: the misfit objective of the
starting model over a source-subsampled set of the survey's shots, each
shot forward-simulated, sampled at its own moving receiver spread and
compared with its recorded data, the misfits summed. ``api`` compiles the
forward → misfit → Σ description, WFProcessor, Emgr and ``JaxRTS`` run it,
one composed ``dag`` carrier a campaign. The shots are every stride-th of
the survey (stride = shots / shots per campaign) from an offset of seed
mod stride; every campaign of a run uses the run's seed, so each one does
the same work. The recorded data, the true model's seismograms of those
shots, are simulated once at set-up.

The check runs ``bench/reference/seismic.py`` once for the run's shots,
on its own models from the seed, and compares every campaign's per-shot
misfits and their device sum with it.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List

import numpy as np

from ..probe import tier_faults, tier_report
from ..reference import seismic as ref

# Limits, each set between the largest reading of sound runs and the
# smallest reading of the bfloat16 control (readings in PERF.md).
LIMITS = {
    "campaign_faults": 0.0,
    "misfit_rel_gap": 1e-4,
    "sum_rel_gap": 1e-4,
}


def campaign_shots(config: Dict[str, Any], seed: int) -> List[int]:
    """Every stride-th shot of the survey from an offset of seed mod stride,
    stride = shots / shots per campaign."""
    stride = config["n_shots"] // config["shots_per_campaign"]
    return list(range(seed % stride, config["n_shots"], stride))[
        :config["shots_per_campaign"]]


def survey(config: Dict[str, Any]):
    """The program's configuration of the survey."""
    from repro.apps.seismic.solver import SeismicConfig, grid_index

    c = config
    offsets = c["near_offset_m"] + c["receiver_spacing_m"] * np.arange(
        c["n_receivers"])
    return SeismicConfig(
        nx=c["nx"], nz=c["nz"], nt=c["nt"], dx=c["dx"], dt=c["dt"],
        f0=c["f0"], n_receivers=c["n_receivers"], damp_width=c["pad"],
        damp_strength=c["damp_strength"], record_every=c["record_every"],
        source_z=int(grid_index(c["source_depth_m"], c["dx"])),
        receiver_z=int(grid_index(c["receiver_depth_m"], c["dx"])),
        pad=c["pad"], c_max=c["c_max"], model="marmousi",
        first_shot_m=c["first_shot_m"],
        shot_spacing_m=c["shot_spacing_m"],
        receiver_offsets_m=tuple(float(-o) for o in offsets))


class Campaigns:
    """The campaigns of one cell: one configuration, one traffic mix."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices) -> None:
        from repro.apps.seismic.solver import shot_geometry
        from repro.apps.seismic.workflow import record_survey

        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.devices = devices
        self.rounds = 1
        self.shots = campaign_shots(config, seed)
        self.survey = survey(config)
        self.geometry = [shot_geometry(self.survey, j) for j in self.shots]
        self.observed = record_survey(self.survey, self.geometry, seed)
        self._refs: Dict[str, Dict[str, Any]] = {}

    def run(self) -> Dict[str, Any]:
        """One campaign: the objective over the run's shots."""
        from repro.apps.seismic.workflow import run_sweep

        res = run_sweep(self.survey, self.geometry, self.observed,
                        seed=self.seed, slots=self.cfg["slots"],
                        devices=self.devices)
        misfits = np.asarray(res["misfits"], np.float64)
        return {"misfits": misfits, "total": float(res["total_misfit"]),
                "faults": (tier_faults(tier_report(res["rts"]),
                                       self.traffic["tier"], self.rounds)
                           + int(not res["all_done"])
                           + abs(len(misfits) - len(self.shots)))}

    # ---- the check ------------------------------------------------------- #

    def reference(self, dtype) -> Dict[str, Any]:
        """The reference's misfits of the run's shots in ``dtype``, computed
        once: every campaign does the same shots."""
        key = np.dtype(dtype).name
        if key not in self._refs:
            self._refs[key] = ref.sweep(self.cfg, self.seed, self.shots,
                                        dtype)
        return self._refs[key]

    def release(self) -> None:
        """Drop the program's models of this configuration, and the
        reference's answers."""
        from repro.apps.seismic import workflow

        for key in [k for k in workflow._CACHE
                    if isinstance(k, tuple) and self.survey in k]:
            del workflow._CACHE[key]
        self._refs.clear()

    @staticmethod
    def compare(outputs: List[Dict[str, Any]], expected: Dict[str, Any]
                ) -> Dict[str, float]:
        """The numbers compared, over every campaign checked."""
        want = expected["misfits"]
        gaps = [float(np.max(np.abs(o["misfits"] - want) / want))
                if len(o["misfits"]) == len(want) else 1.0
                for o in outputs]
        return {"campaign_faults": float(sum(o["faults"] for o in outputs)),
                "misfit_rel_gap": max(gaps),
                "sum_rel_gap": max(abs(o["total"] - expected["total"])
                                   / expected["total"] for o in outputs)}

    def check(self, outputs: List[Dict[str, Any]]) -> Dict[str, float]:
        import jax.numpy as jnp

        expected = self.reference(jnp.float32)
        share = expected["misfits"] / expected["energies"]
        print(f"bench: seismic misfit / recorded energy per shot: "
              f"min {share.min():.6g}, max {share.max():.6g}",
              file=sys.stderr, flush=True)
        return self.compare(outputs, expected)

    def control(self, outputs: List[Dict[str, Any]]) -> Dict[str, float]:
        """The numbers that the reference in bfloat16, put in the
        program's place, reads against the float32 reference."""
        import jax.numpy as jnp

        low = self.reference(jnp.bfloat16)
        swapped = [dict(o, misfits=low["misfits"], total=low["total"])
                   for o in outputs]
        return self.compare(swapped, self.reference(jnp.float32))
