"""AnEn campaigns (paper §III-B) driven through the program's entry points.

Each campaign is one ``run_adaptive`` (AUA) or ``run_random`` call: the
declarative loop compiled by ``api``, its rounds run by WFProcessor, Emgr
and ``JaxRTS``, each round one composed carrier (Pallas distance kernel,
top-k, mean) on the tier the traffic names, the estimate interpolated by
the jitted IDW between rounds, the next round placed by the host proposal.
Every campaign of a run uses the run's seed, so each one does the same
work; none reads another's outputs.

The check runs ``bench/reference/anen.py`` on its own data from the seed
and compares, for every campaign checked, each analog value, the final
RMSE, and the locations each round placed.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..probe import tier_faults, tier_report
from ..reference import anen as ref

# A value agrees with the reference when it lies within this of the range
# of means that the reference's analogs give, ties at the k-th distance
# resolved either way: float32 rounding of a mean of k = 12 observations of
# magnitude below 5 is a few 1e-6, while one analog swapped outside a tie
# moves the mean by a whole observation difference / 12 (about 1e-2).
VALUE_ATOL = 1e-4

# Limits, each set between the largest reading of sound runs and the
# smallest reading of the bfloat16 control (readings in PERF.md).
LIMITS = {
    "campaign_faults": 0.0,
    "analog_mismatches": 0.0,
    "rmse_rel_gap": 1e-4,
    "placement_mismatch": 0.05,
}


class Campaigns:
    """The campaigns of one cell: one configuration, one traffic mix."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices) -> None:
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.devices = devices
        self.rounds = config["max_iters"]
        self._refs: Dict[tuple, Dict[str, Any]] = {}
        self._data: Optional[Dict[str, np.ndarray]] = None

    def run(self) -> Dict[str, Any]:
        """One campaign, to its final estimate."""
        from repro.apps.anen.workflow import run_adaptive, run_random

        c = self.cfg
        method = {"aua": run_adaptive, "random": run_random}
        res = method[self.traffic["method"]](
            seed=self.seed, ny=c["ny"], nx=c["nx"], n_hist=c["n_hist"],
            per_iter=c["per_iter"], max_iters=c["max_iters"],
            n_tasks=c["n_tasks"], slots=c["slots"], devices=self.devices)
        locations = np.asarray(res["locations"], np.int32).reshape(-1, 2)
        return {"locations": locations,
                "values": np.asarray(res["values"], np.float32),
                "final_rmse": float(res["final_rmse"]),
                "faults": self._faults(res, locations)}

    def _faults(self, res: Dict[str, Any], locations: np.ndarray) -> int:
        """Exact checks of one campaign: it ran every round on the intended
        tier, and placed the stated number of distinct on-grid locations."""
        c = self.cfg
        expected = c["per_iter"] * c["max_iters"]
        inside = ((locations[:, 0] >= 0) & (locations[:, 0] < c["ny"])
                  & (locations[:, 1] >= 0) & (locations[:, 1] < c["nx"]))
        distinct = len(np.unique(locations, axis=0))
        return (tier_faults(tier_report(res["rts"]), self.traffic["tier"],
                            self.rounds)
                + int(not res["all_done"])
                + abs(self.rounds - res["rounds"])
                + abs(expected - len(locations))
                + int((~inside).sum()) + (len(locations) - distinct)
                + abs(len(res["values"]) - len(locations)))

    # ---- the check ------------------------------------------------------- #

    def data(self) -> Dict[str, np.ndarray]:
        """The reference's own data for this seed, built once."""
        if self._data is None:
            c = self.cfg
            self._data = ref.dataset(self.seed, c["ny"], c["nx"], c["n_hist"])
        return self._data

    def reference(self, outputs: List[Dict[str, Any]], dtype
                  ) -> List[Dict[str, Any]]:
        """What each campaign should have answered given the locations its
        rounds placed, computed by the reference in ``dtype``."""
        c = self.cfg
        answers = []
        for out in outputs:
            key = (np.dtype(dtype).name, out["locations"].tobytes())
            if key not in self._refs:
                self._refs[key] = ref.campaign_answers(
                    self.data(), out["locations"], k=c["k"], idw=c["idw"],
                    method=self.traffic["method"], seed=self.seed,
                    per_iter=c["per_iter"], rounds=self.rounds, dtype=dtype)
            answers.append(self._refs[key])
        return answers

    def release(self) -> None:
        """Drop the program's copy of this seed's data from the device, and
        the reference's."""
        from repro.apps.anen import workflow

        c = self.cfg
        workflow._DATASETS.pop((self.seed, c["ny"], c["nx"], c["n_hist"]),
                               None)
        self._refs.clear()
        self._data = None

    def compare(self, outputs: List[Dict[str, Any]],
                expected: List[Dict[str, Any]]) -> Dict[str, float]:
        """The numbers compared, over every campaign checked."""
        mismatches = sum(int(np.sum((o["values"] < e["lo"] - VALUE_ATOL)
                                    | (o["values"] > e["hi"] + VALUE_ATOL)))
                         if len(o["values"]) == len(e["lo"])
                         else len(e["lo"])
                         for o, e in zip(outputs, expected))
        rmse_gap = max(abs(o["final_rmse"] - e["final_rmse"])
                       / e["final_rmse"] for o, e in zip(outputs, expected))
        return {"campaign_faults": float(sum(o["faults"] for o in outputs)),
                "analog_mismatches": float(mismatches),
                "rmse_rel_gap": float(rmse_gap),
                "placement_mismatch": max(
                    self._misplaced(o, e) for o, e in zip(outputs, expected))}

    def _misplaced(self, out: Dict[str, Any], due: Dict[str, Any]) -> float:
        """The largest share of a round's locations that the reference's
        placement of that round does not hold."""
        per_iter = self.cfg["per_iter"]
        rounds = out.get("placements") or [
            out["locations"][r * per_iter:(r + 1) * per_iter]
            for r in range(self.rounds)]
        worst = 0.0
        for got, want in zip(rounds, due["placements"]):
            want = set(map(tuple, np.asarray(want).tolist()))
            missed = sum(tuple(p) not in want
                         for p in np.asarray(got).tolist())
            worst = max(worst, (missed + abs(per_iter - len(got)))
                        / per_iter)
        return float(worst)

    def check(self, outputs: List[Dict[str, Any]]) -> Dict[str, float]:
        import jax.numpy as jnp

        return self.compare(outputs, self.reference(outputs, jnp.float32))

    def control(self, outputs: List[Dict[str, Any]]) -> Dict[str, float]:
        """The numbers that the reference in bfloat16, put in the
        program's place, reads against the float32 reference."""
        import jax.numpy as jnp

        low = self.reference(outputs, jnp.bfloat16)
        swapped = [dict(o, values=l["values"], final_rmse=l["final_rmse"],
                        placements=l["placements"])
                   for o, l in zip(outputs, low)]
        return self.compare(swapped, self.reference(outputs, jnp.float32))
