#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py ... --rehearse     # tiny sizes on the CPU

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration's ``app`` names the
module (``bench/apps/<app>.py``) that runs its campaigns through the
program's entry points. The run builds everything from ``--seed``, warms up
with one campaign at the cell's shapes (set-up), then runs campaigns back
to back for ``--seconds`` (the campaign in flight then completes), checks
every output of the warm-up and the window against the plain reference,
and prints one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, each from ``bench/end_to_end/<name>.py`` or
``bench/layer_metrics/<name>.py``), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared beside its limit.
Those numbers also end standard error.

Without ``--rehearse`` the run needs a TPU: it exits 2, printing nothing on
standard output, where JAX finds none or fewer chips than the cell asks
for. ``--rehearse`` runs the configuration's ``rehearsal`` sizes on the CPU
(with as many virtual devices as the cell has chips); its numbers are CPU
numbers and its device line names the CPU.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is timed from the process's first line

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def _say(*parts: Any) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def load_cell(workload: str) -> Dict[str, Any]:
    """The cell, its configuration, its traffic and its metrics."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    if traffic["app"] != config["app"]:
        raise SystemExit(f"bench: traffic {cell['traffic']!r} is for "
                         f"{traffic['app']!r}, not {config['app']!r}")

    def mine(metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics if workload in m.get("workloads",
                                                        [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def prepare(workload: str, rehearse: bool) -> Dict[str, Any]:
    """Load the cell and set the process up before JAX starts: the
    program on the path, the TPU's logs off the disk, and for a rehearsal
    the CPU with one virtual device per chip and the rehearsal sizes."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    run = load_cell(workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count="
                + str(run["cell"]["chips"]))
        run["config"].update(run["config"]["rehearsal"])
    else:
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
    return run


def _tpu_attached() -> bool:
    """A TPU chip on the PCI bus (JAX's own probe) or an accelerator device
    node, decided without starting a JAX backend: a TPU backend that finds
    no chip goes on to look for a cloud metadata server."""
    from jax._src import hardware_utils
    return (hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0
            or bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")))


def _enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@contextlib.contextmanager
def _traced(enabled: bool, state: Dict[str, Any]):
    """With ``enabled``: the profiler (Python tracer off) and the
    program's spans around the window; the trace file and the spans land
    in ``state``."""
    if not enabled:
        yield
        return
    import jax
    from repro import telemetry

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        telemetry.enable(ring_size=1 << 20)
        telemetry.TRACER.clear()
        try:
            yield
        finally:
            telemetry.disable()
            jax.profiler.stop_trace()
            state["spans"] = telemetry.TRACER.snapshot()
            state["dropped_spans"] = telemetry.TRACER.dropped_spans
            from bench import trace as tr
            paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
            state["trace"] = tr.load(paths[0]) if paths else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's rehearsal sizes on the CPU")
    args = ap.parse_args(argv)

    run = prepare(args.workload, args.rehearse)
    chips = run["cell"]["chips"]
    if not args.rehearse and not _tpu_attached():
        _say("no TPU chip attached")
        return 2

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _say(f"JAX found no device: {e}")
        return 2
    kind = devices[0].device_kind
    if not args.rehearse and devices[0].platform != "tpu":
        _say(f"platform {devices[0].platform!r} is not a TPU")
        return 2
    if len(devices) < chips:
        _say(f"{chips} chips needed, {len(devices)} found")
        return 2
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if not args.rehearse and kind not in peaks:
        _say(f"device kind {kind!r} is not in bench/peaks.json")
        return 2
    if not args.rehearse:
        _say("compile cache", _enable_compile_cache())

    import repro  # noqa: F401 - the system under test must be there
    from bench import layers
    from bench.probe import CompileMeter

    app = importlib.import_module(f"bench.apps.{run['config']['app']}")
    used = devices[:chips]
    campaigns = app.Campaigns(run["config"], run["traffic"], args.seed, used)
    warm_up: List[Dict[str, Any]] = []
    outputs: List[Dict[str, Any]] = []
    errors: List[str] = []
    with CompileMeter() as warm:
        try:
            warm_up.append(campaigns.run())      # checked, not timed
        except Exception:  # noqa: BLE001 - the run reports it
            errors.append(traceback.format_exc(limit=8))
    setup_s = time.perf_counter() - T0
    _say(f"set-up {setup_s:.3f} s, {warm.compiles} compiles "
         f"({warm.seconds:.3f} s) in the warm-up")

    traced: Dict[str, Any] = {}
    with CompileMeter() as window_meter, _traced(args.trace == 1, traced):
        with jax.profiler.TraceAnnotation("bench.window"):
            mono_at_lo = time.monotonic_ns()
            t0 = time.perf_counter()
            while not errors:
                try:
                    with jax.profiler.TraceAnnotation("bench.campaign"):
                        outputs.append(campaigns.run())
                except Exception:  # noqa: BLE001 - the run reports it
                    errors.append(traceback.format_exc(limit=8))
                    break
                if time.perf_counter() - t0 >= args.seconds:
                    break
            elapsed = time.perf_counter() - t0
    memory_peak = _memory_peak(devices)
    _say(f"window {elapsed:.3f} s, {len(outputs)} campaigns, "
         f"{window_meter.compiles} compiles")
    gc.collect()

    numbers: Dict[str, float] = {}
    try:
        if outputs:
            numbers = campaigns.check(warm_up + outputs)
    except Exception:  # noqa: BLE001 - the check failed: not correct
        errors.append(traceback.format_exc(limit=8))
    for e in errors:
        _say(e)
    correct = (not errors and bool(outputs)
               and set(numbers) == set(app.LIMITS)
               and all(numbers[k] <= app.LIMITS[k] for k in numbers))

    window = layers.Window(
        config=run["config"], traffic=run["traffic"], setup_s=setup_s,
        seconds=elapsed, campaigns=len(outputs),
        rounds=len(outputs) * campaigns.rounds,
        compiles=window_meter.compiles, memory_peak_bytes=memory_peak,
        spans=traced.get("spans", []), trace=traced.get("trace"),
        devices=[d.id for d in used], peak=peaks.get(kind))
    kind_of = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in run[kind_of]:
        value = layers.read(kind_of, m["name"], window)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak or 0}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(outputs) + len(errors[:1]),
        "failed": sum(1 for o in outputs if o["faults"]) + len(errors[:1]),
        "metrics": metrics, "device": device}
    if args.trace:
        busy = layers.busy_ns(window)
        if window.bounds is not None:
            lo, hi = window.bounds
            device["busy_s"] = (sum(busy) / len(busy) * 1e-9 if busy
                                else 0.0)
            device["window_s"] = (hi - lo) * 1e-9
            result["breakdown"] = layers.breakdown(window, mono_at_lo)
        _say(f"{len(window.spans)} program spans, "
             f"{traced.get('dropped_spans', 0)} dropped")
    result["check"] = {k: {"value": numbers.get(k), "limit": v}
                       for k, v in app.LIMITS.items()}
    for k, v in result["check"].items():
        _say(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
