#!/usr/bin/env python3
"""Read the numbers the check compares, for the program and the control.

    python3 bench/calibrate.py --workload <cell> --seeds 11 12 13 [--rehearse]

For each seed, in one process: one campaign of the cell at its own size
through the program's entry point, the numbers of ``check`` for it (the
program against the float32 reference), and the same numbers for the
control: the reference computed in bfloat16 and put in the program's place.
The limits in ``bench/apps/<app>.py`` lie between the largest program
reading and the smallest control reading; the benchmark's own runs never
run the control. Prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.run import _enable_compile_cache, prepare

    run = prepare(args.workload, args.rehearse)
    chips = run["cell"]["chips"]
    import jax

    if not args.rehearse:
        _enable_compile_cache()
    app = importlib.import_module(f"bench.apps.{run['config']['app']}")
    for seed in args.seeds:
        campaigns = app.Campaigns(run["config"], run["traffic"], seed,
                                  jax.devices()[:chips])
        t0 = time.perf_counter()
        outputs = [campaigns.run()]
        t1 = time.perf_counter()
        program = campaigns.check(outputs)
        t2 = time.perf_counter()
        control = campaigns.control(outputs)
        t3 = time.perf_counter()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control,
                          "limits": app.LIMITS,
                          "campaign_s": t1 - t0, "check_s": t2 - t1,
                          "control_s": t3 - t2}), flush=True)
        campaigns.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
