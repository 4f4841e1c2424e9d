"""chip_smoke.py's phases in its CPU rehearsal mode, at tiny sizes.

The rehearsal drives the same path the chip run does (api → compiler →
WFProcessor → Emgr → JaxRTS → fusion engine) and applies the same checks:
values against the scalar reference, and every carrier on the intended
tier. It never reports a TPU.
"""

import importlib.util
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _records(text):
    return [json.loads(line) for line in text.strip().splitlines()]


def test_rehearsal_runs_both_campaigns_on_the_dag_tier(capsys):
    smoke = _load()
    assert smoke.main(["--rehearse"]) == 0
    records = _records(capsys.readouterr().out)
    last = records[-1]
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    phases = {r["phase"]: r for r in records[:-1]}
    assert phases["setup"]["rehearsal"] is True
    anen, seismic = phases["anen"], phases["seismic"]
    for report in (anen, seismic):
        assert report["failed"] == []
        for run in ("cold", "steady"):
            stats = report[run]["fusion_stats"]
            assert stats["degraded"] == 0 and stats["scalar_fallback"] == 0
            assert report[run]["breakers_opened"] == 0
        assert report["steady_compiles"] == 0
    # one composed DAG carrier per adaptive round, one for the sweep
    assert anen["rounds"] == smoke.ANEN_TINY["max_iters"]
    assert anen["cold"]["carriers"] == {"dag": anen["rounds"]}
    assert anen["cold"]["fusion_stats"]["dag_carriers"] == anen["rounds"]
    assert seismic["cold"]["carriers"] == {"dag": 1}
    assert anen["drift"]["agree_share"] >= smoke.ANEN_AGREE_SHARE
    assert seismic["drift"]["max_rel_drift_members"] <= smoke.SEISMIC_RTOL
    assert "tpu" not in json.dumps(records)


def test_rehearsal_sharded_round_spans_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--rehearse", "--chips", "4"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr
    records = _records(proc.stdout)
    assert records[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    (report,) = [r for r in records if r.get("phase") == "anen-sharded"]
    assert report["failed"] == []
    assert report["mesh_shards"] == [4]
    assert report["mesh"]["fusion_stats"]["sharded_dispatches"] > 0
    assert report["mesh"]["carriers"] == {"dag-shard": 1}
    assert report["one_device"]["carriers"] == {"dag": 1}
    assert report["drift"]["vs_one_device_agree_share"] == 1.0


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                          text=True, timeout=120, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "tpu" not in proc.stdout.lower()
