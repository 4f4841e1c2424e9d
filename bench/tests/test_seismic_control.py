"""The seismic check's control, and faults planted under a whole run.

At the rehearsal sizes on the CPU:

* the control, the reference computed in bfloat16 and put in the program's
  place, fails the check that sound outputs pass;
* a whole run (set-up, window, check, result line) with the timed path
  broken underneath reports ``correct`` false, once for each fault: the
  trial model set to the true one (every misfit about 0), one receiver
  spread for every shot, the record taken every step instead of every
  ``record_every``-th, half of the shots' misfits replaced by the mean of
  the rest.

The chip runs the control at the cell's own size through
``bench/calibrate.py``.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "seismic_sweep.1chip"


def test_the_control_fails_where_the_program_passes():
    import jax
    from bench.apps import seismic as app
    from bench.run import load_cell

    run = load_cell(CELL)
    run["config"].update(run["config"]["rehearsal"])
    campaigns = app.Campaigns(run["config"], run["traffic"], 7,
                              jax.devices()[:1])
    outputs = [campaigns.run()]
    program = campaigns.check(outputs)
    control = campaigns.control(outputs)
    campaigns.release()
    assert all(program[k] <= app.LIMITS[k] for k in program)
    assert any(control[k] > app.LIMITS[k] for k in control)


FAULTS = {
    # the trial model is the true one: the recorded data come back, every
    # misfit about 0
    "trial_is_true": """
        import repro.apps.seismic.workflow as w
        real = w._velocity
        w._velocity = lambda kind, cfg, seed: real("true", cfg, seed)
    """,
    # every shot recorded at the first shot's receivers
    "fixed_spread": """
        import repro.apps.seismic.solver as s
        real = s.shot_geometry
        first = {}
        def shot_geometry(cfg, shot):
            source, receivers = real(cfg, shot)
            return source, first.setdefault(cfg, receivers)
        s.shot_geometry = shot_geometry
    """,
    # every step recorded, the record cut to its length
    "undecimated": """
        import dataclasses
        import repro.apps.seismic.workflow as w
        real = w.forward_simulation
        def forward_simulation(velocity, source_x, cfg, receiver_x=None):
            every = dataclasses.replace(
                cfg, nt=cfg.nt // cfg.record_every, record_every=1)
            return real(velocity, source_x, every, receiver_x)
        w.forward_simulation = forward_simulation
    """,
    # half of the shots' misfits left out, the mean of the rest in their
    # place
    "half_batch": """
        import dataclasses
        import jax.numpy as jnp
        import repro.apps.seismic.workflow as w
        from repro.fusion.groups import FUSION_ATTR, fusion_spec
        spec = fusion_spec(w.shot_misfit)
        def batched(synthetic, observed):
            out = 0.5 * jnp.sum((synthetic - observed) ** 2, axis=(1, 2))
            half = out.shape[0] // 2
            return out.at[half:].set(out[:half].mean())
        setattr(w.shot_misfit, FUSION_ATTR,
                dataclasses.replace(spec, batched=batched))
    """,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(fault):
    code = textwrap.dedent(FAULTS[fault]) + textwrap.dedent(f"""
        import sys
        from bench.run import main
        sys.exit(main(["--workload", "{CELL}", "--seed", "11",
                       "--seconds", "0.3", "--rehearse"]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=240, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["check"]
    assert result["check"]["campaign_faults"]["value"] == 0
