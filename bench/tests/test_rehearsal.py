"""Every cell of BENCHMARK.json, rehearsed at tiny sizes on the CPU.

Each rehearsal is the whole run (set-up, window, check, result line) in its
own process, as on the chip, with the configuration's ``rehearsal`` sizes
and as many virtual CPU devices as the cell asks chips for.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]


def _run(workload, trace, cwd=ROOT, rehearse=True, seed=2_147_483_659):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "bench/run.py", "--workload", workload,
           "--seed", str(seed), "--seconds", "0.3", "--trace", str(trace)]
    return subprocess.run(cmd + (["--rehearse"] if rehearse else []),
                          capture_output=True, text=True, timeout=240,
                          env=env, cwd=cwd)


def _metrics_of(workload, kind):
    return {m["name"] for m in SPEC[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_last_line_is_a_correct_cpu_result(workload):
    proc = _run(workload, trace=0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(result)[-1] == "check"
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    chips = {c["name"]: c["chips"] for c in SPEC["workloads"]}[workload]
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert "tpu" not in proc.stdout.lower()
    # every end-to-end metric of the cell but the device's memory peak,
    # which the CPU does not report
    assert set(result["metrics"]) == (_metrics_of(workload, "end_to_end")
                                      - {"peak_hbm_gib"})
    for number in result["check"].values():
        assert number["value"] <= number["limit"]
    # the numbers compared also end standard error
    tail = proc.stderr.strip().splitlines()[-len(result["check"]):]
    assert all(line.startswith("bench: check ") for line in tail)


@pytest.mark.parametrize("workload", ["anen_aua.1chip",
                                      "anen_random.1chip"])
def test_traced_rehearsal_reads_the_program_layers(workload):
    proc = _run(workload, trace=1)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    # on the CPU the trace has no device plane: only the program's spans
    # and JAX's compile counter have something to read
    names = set(result["metrics"])
    assert names <= _metrics_of(workload, "per_layer")
    assert "carrier_dispatch_ms.adaptive" in names
    assert result["metrics"]["window_compiles.adaptive"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_refuses_to_run_without_a_tpu():
    proc = _run("anen_aua.1chip", trace=0, rehearse=False)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run("anen_aua.1chip", trace=0, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
