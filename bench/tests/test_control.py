"""The check's control, and faults planted under a whole run.

At the rehearsal sizes on the CPU:

* the control, the reference computed in bfloat16 and put in the program's
  place, fails the check that sound outputs pass;
* a whole run (set-up, window, check, result line) with the timed path
  broken underneath reports ``correct`` false, once for each fault a
  campaign can have: an answer altered where it is produced, half of a
  batch left out with the mean of the rest in its place, a round whose
  state is left unchanged, locations placed other than the proposal
  places them. (The only exchange between chips in these cells, the
  round's spread maximum, is not an output of a campaign.)

The chip runs the control at the cells' own sizes through
``bench/calibrate.py``.
"""

import importlib
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload", ["anen_aua.1chip",
                                      "anen_random.1chip"])
def test_the_control_fails_where_the_program_passes(workload):
    import jax
    from bench.run import load_cell

    run = load_cell(workload)
    run["config"].update(run["config"]["rehearsal"])
    app = importlib.import_module(f"bench.apps.{run['config']['app']}")
    campaigns = app.Campaigns(run["config"], run["traffic"], 7,
                              jax.devices()[:1])
    outputs = [campaigns.run()]
    program = campaigns.check(outputs)
    control = campaigns.control(outputs)
    campaigns.release()
    assert all(program[k] <= app.LIMITS[k] for k in program)
    assert any(control[k] > app.LIMITS[k] for k in control)


FAULTS = {
    # one analog answer altered where the kernel produces it: the first
    # location of every round takes its farthest analogs
    "anen_answer_altered": ("anen_aua.1chip", """
        import repro.kernels.anen_distance as k
        real = k.anen_distance
        def anen_distance(f_hist, f_now, **kw):
            d2 = real(f_hist, f_now, **kw)
            return d2.at[:, 0].multiply(-1.0)
        k.anen_distance = anen_distance
    """),
    # half of each batch of members left out, the mean of the rest in
    # their place
    "anen_half_batch": ("anen_random.1chip", """
        import repro.apps.anen.workflow as w
        from repro.fusion.groups import FUSION_ATTR, fusion_spec
        import dataclasses
        spec = fusion_spec(w.analog_values)
        real = spec.batched
        def batched(locations, **kw):
            out = real(locations, **kw)
            half = out.shape[0] // 2
            return out.at[half:].set(out[:half].mean())
        setattr(w.analog_values, FUSION_ATTR,
                dataclasses.replace(spec, batched=batched))
    """),
    # a round that leaves the campaign's state unchanged
    "anen_state_unchanged": ("anen_aua.1chip", """
        import repro.apps.anen.workflow as w
        def absorb(self, results):
            self.iteration += 1
            self.errors.append(self.errors[-1] if self.errors else 1.0)
        w._SearchState.absorb = absorb
    """),
    # the AUA proposal's greedy picks replaced by uniform draws from the
    # same stream: every answer right at the locations placed, the wrong
    # locations placed
    "anen_placement_random": ("anen_aua.1chip", """
        import repro.apps.anen.workflow as w
        w._SearchState._adaptive_new = lambda self, n: self._random_new(n)
    """),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(fault):
    workload, patch = FAULTS[fault]
    code = textwrap.dedent(patch) + textwrap.dedent(f"""
        import sys
        from bench.run import main
        sys.exit(main(["--workload", "{workload}", "--seed", "11",
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
