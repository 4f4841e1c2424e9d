#!/usr/bin/env python3
"""Run both paper campaigns end to end on one TPU chip.

    python chip_smoke.py                       # AnEn + seismic, one TPU chip
    python chip_smoke.py --chips 4             # the sharded AnEn round on a
                                               # 4-chip mesh vs one chip
    python chip_smoke.py --rehearse [--chips 4]  # tiny sizes on the CPU

Every phase drives the normal path (``api`` → compiler → WFProcessor → Emgr
→ ``JaxRTS`` → fusion engine), compares its timed outputs with the scalar
reference under the tolerances below, and checks that the work ran on the
intended tier: no degraded carrier, no scalar fallback, no opened breaker.
Each earlier stdout line is one JSON object; the last line is
``{"ok": true, "device": {...}}`` with the device as JAX reports it. A failed
check exits non-zero before that line.

Without ``--rehearse`` the script sets ``JAX_PLATFORMS=tpu`` when it is
unset, so JAX raises instead of falling back to the CPU, and refuses any
platform but ``tpu``. ``--rehearse`` runs the same phases at tiny sizes on
the CPU (Pallas in interpret mode, four virtual devices for ``--chips 4``);
its numbers are CPU numbers and its last line names the CPU.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent

# The AnEn campaign (paper §III-B) on the NCEP NAM 12 km CONUS grid 218
# (428 x 614 points), a year of daily forecast/observation pairs, the
# generator's 3 predictor variables and k = 12 analogs; 64 members per round
# over 1024 new locations, 4 rounds. The IDW, which scores each round's 1024
# new locations over the grid and keeps every cell's 8 nearest (16.8 MB),
# and the 1.5 GB history fit one v5e's 16 GB.
ANEN = dict(ny=428, nx=614, n_hist=365, n_tasks=64, per_iter=1024,
            max_iters=4)
# The seismic misfit sweep (paper §III-A): the 128 events of Fig. 10 on a
# 512 x 512 grid at 10 m, 2000 steps of 1 ms.
SEISMIC = dict(n_events=128, nx=512, nt=2000)
# the sharded phase: one 64-member AnEn round at the full data size
ANEN_SHARDED = dict(ANEN, max_iters=1)

ANEN_TINY = dict(ny=24, nx=32, n_hist=40, n_tasks=8, per_iter=64,
                 max_iters=2)
SEISMIC_TINY = dict(n_events=8, nx=48, nt=60)
# 64 members: the sharded tier's shard_min_members
ANEN_SHARDED_TINY = dict(ANEN_TINY, n_tasks=64, per_iter=128, max_iters=1)

K = 12              # analogs averaged (the AnEnConfig default)
SEED = 0
SLOTS = 4

# AnEn: the Pallas distance and the jnp reference sum the same three
# squared differences, so agreeing analogs match to float32 rounding of
# O(1) values. A near-tie in the 12th-nearest distance can swap one analog
# and move that location's mean by a whole observation difference / 12, so
# a share of the sampled locations must agree rather than all of them.
ANEN_ATOL = 1e-4
ANEN_AGREE_SHARE = 0.99
ANEN_SAMPLE = 512
# seismic: the fused chain and the scalar kernel run the same float32
# recurrence (2 x 2000 leapfrog steps) in different program fusions;
# rounding differences stay far below a bf16-sized error (~1e-2)
SEISMIC_RTOL = 1e-3
SEISMIC_SAMPLE = 8
# Σ: the device-side float32 sum of 128 misfits vs the host float64 sum
SIGMA_RTOL = 1e-4


def _emit(record: Dict[str, Any]) -> None:
    print(json.dumps(record), flush=True)


class CompileMeter:
    """Counts XLA compiles, their seconds and persistent-cache hits while
    open (JAX's monitoring events; carrier threads compile too). A program
    loaded from the persistent cache counts as a compile of its load time."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.slowest: List[List[Any]] = []

    def _on_duration(self, event: str, duration: float, fun_name: str = "?",
                     **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.compile_s += duration
                self.slowest = sorted(self.slowest + [[fun_name, duration]],
                                      key=lambda e: -e[1])[:3]

    def _on_event(self, event: str, **_: Any) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def report(self, prefix: str) -> Dict[str, Any]:
        return {f"{prefix}_compiles": self.compiles,
                f"{prefix}_compile_s": self.compile_s,
                f"{prefix}_cache_hits": self.cache_hits,
                f"{prefix}_cache_misses": self.cache_misses,
                f"{prefix}_slowest_compiles": self.slowest}


def device_record() -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes() -> List[Any]:
    """``peak_bytes_in_use`` of every device (None where not reported)."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def tier_report(rts) -> Dict[str, Any]:
    """What the runtime executed: fusion counters, carriers per tier and
    breakers that opened."""
    from repro.core.policies import BREAKER_TRANSITIONS
    from repro.rts.jax_rts import CARRIERS_TOTAL
    carriers = {labels["tier"]: c.value
                for labels, c in rts.metrics.collect("counter",
                                                     CARRIERS_TOTAL)}
    opened = sum(c.value for labels, c in rts.metrics.collect(
        "counter", BREAKER_TRANSITIONS) if labels.get("to") == "open")
    return {"fusion_stats": rts.fusion_stats, "carriers": carriers,
            "breakers_opened": opened}


def _tier_checks(tiers: Dict[str, Any], tier: str, min_carriers: int,
                 failed: List[str]) -> None:
    stats = tiers["fusion_stats"]
    if stats["degraded"]:
        failed.append("degraded carriers")
    if stats["scalar_fallback"]:
        failed.append("scalar fallback")
    if tiers["breakers_opened"]:
        failed.append("a breaker opened")
    if tiers["carriers"].get(tier, 0) < min_carriers:
        failed.append(f"fewer than {min_carriers} {tier} carriers")


def _anen_agreement(data, locations, values, seed: int) -> Dict[str, Any]:
    """Sampled locations' analogs against the scalar ``compute_analogs``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.anen.anen import compute_analogs

    locs = np.asarray(locations, np.int32)
    vals = np.asarray(values, np.float32)
    pick = np.random.default_rng(seed).choice(
        len(locs), size=min(ANEN_SAMPLE, len(locs)), replace=False)
    ref = np.asarray(compute_analogs(data, jnp.asarray(locs[pick]), K))
    diff = np.abs(ref - vals[pick])
    agree = diff <= ANEN_ATOL
    return {"sampled": int(len(pick)), "agree_share": float(agree.mean()),
            "max_abs_drift_agreeing": float(diff[agree].max())
            if agree.any() else None,
            "max_abs_drift": float(diff.max())}


def _round_holds_kernel(size: Dict[str, int], seed: int) -> bool:
    """Lower the round's batched member program at the run's shapes (the
    dataset as abstract arguments) and look for the Pallas custom call."""
    import jax
    import jax.numpy as jnp
    from repro.apps.anen.workflow import (_analog_values_batched,
                                          _dataset_operands)

    statics = dict(seed=seed, ny=size["ny"], nx=size["nx"],
                   n_hist=size["n_hist"], k=K)
    operands = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in _dataset_operands(**statics).items()}
    locations = jax.ShapeDtypeStruct(
        (size["n_tasks"], size["per_iter"] // size["n_tasks"], 2), jnp.int32)
    program = jax.jit(lambda loc, ops: _analog_values_batched(
        loc, **statics, **ops))
    return "tpu_custom_call" in program.lower(locations, operands).as_text()


def anen_phase(size: Dict[str, int], seed: int = SEED) -> Dict[str, Any]:
    """The adaptive (AUA) campaign through ``run_adaptive``: a cold run
    (compiles included) checked against the reference, then a warm rerun
    for the steady wall time."""
    import jax
    from repro.apps.anen.workflow import _dataset, run_adaptive

    report: Dict[str, Any] = {"phase": "anen", **size, "k": K}
    failed: List[str] = []
    t0 = time.perf_counter()
    data = _dataset(seed, size["ny"], size["nx"], size["n_hist"])
    jax.block_until_ready(data)
    report["setup_s"] = time.perf_counter() - t0
    report["dataset_bytes"] = sum(int(a.nbytes) for a in data)
    n_locations = size["per_iter"] * size["max_iters"]
    report["idw_working_set_bytes"] = size["ny"] * size["nx"] * n_locations * 4
    kw = dict(ny=size["ny"], nx=size["nx"], n_hist=size["n_hist"],
              per_iter=size["per_iter"], max_iters=size["max_iters"],
              n_tasks=size["n_tasks"], slots=SLOTS)
    with CompileMeter() as cold:
        t0 = time.perf_counter()
        res = run_adaptive(seed=seed, **kw)
        report["cold_wall_s"] = time.perf_counter() - t0
    report.update(cold.report("cold"))
    with CompileMeter() as warm:
        t0 = time.perf_counter()
        steady = run_adaptive(seed=seed, **kw)
        report["steady_wall_s"] = time.perf_counter() - t0
    report.update(warm.report("steady"))
    report["rounds"] = res["rounds"]
    report["locations"] = res["n_locations"]
    report["final_rmse"] = res["final_rmse"]
    report["cold"] = tier_report(res["rts"])
    report["steady"] = tier_report(steady["rts"])
    for run in (res, steady):
        if not run["all_done"] or run["rounds"] != size["max_iters"]:
            failed.append("campaign did not finish its rounds")
        if run["n_locations"] != n_locations:
            failed.append("campaign computed the wrong number of locations")
    for key in ("cold", "steady"):
        _tier_checks(report[key], "dag", size["max_iters"], failed)
    report["drift"] = _anen_agreement(data, res["locations"], res["values"],
                                      seed)
    if report["drift"]["agree_share"] < ANEN_AGREE_SHARE:
        failed.append("analogs disagree with compute_analogs")
    if jax.default_backend() == "tpu":
        report["round_holds_tpu_custom_call"] = _round_holds_kernel(size,
                                                                    seed)
        if not report["round_holds_tpu_custom_call"]:
            failed.append("the round program holds no tpu_custom_call")
    report["peak_bytes_in_use"] = peak_bytes()
    report["failed"] = failed
    return report


def seismic_phase(size: Dict[str, int], seed: int = SEED) -> Dict[str, Any]:
    """The forward → misfit → Σ sweep through ``run_misfit_chain``, checked
    against per-member scalar ``eval_misfit`` on a few events and against
    the host sum of the delivered misfits."""
    import jax
    import numpy as np
    from repro.apps.seismic.solver import SeismicConfig
    from repro.apps.seismic.workflow import (_velocity, eval_misfit,
                                             run_misfit_chain)

    n, nx, nt = size["n_events"], size["nx"], size["nt"]
    report: Dict[str, Any] = {"phase": "seismic", **size}
    failed: List[str] = []
    cfg = SeismicConfig(nx=nx, nz=nx, nt=nt)
    t0 = time.perf_counter()
    vel_true = jax.block_until_ready(_velocity("true", cfg, seed))
    jax.block_until_ready(_velocity("init", cfg, seed))
    report["setup_s"] = time.perf_counter() - t0
    report["cfl"] = float(vel_true.max()) * cfg.dt / cfg.dx
    with CompileMeter() as cold:
        t0 = time.perf_counter()
        res = run_misfit_chain(n, slots=SLOTS, nx=nx, nt=nt, seed=seed)
        report["cold_wall_s"] = time.perf_counter() - t0
    report.update(cold.report("cold"))
    with CompileMeter() as warm:
        t0 = time.perf_counter()
        steady = run_misfit_chain(n, slots=SLOTS, nx=nx, nt=nt, seed=seed)
        report["steady_wall_s"] = time.perf_counter() - t0
    report.update(warm.report("steady"))
    report["cold"] = tier_report(res["rts"])
    report["steady"] = tier_report(steady["rts"])
    for run in (res, steady):
        if not run["all_done"]:
            failed.append("sweep did not finish")
    for key in ("cold", "steady"):
        _tier_checks(report[key], "dag", 1, failed)

    xs = np.linspace(8, nx - 9, n).astype(int)
    pick = np.linspace(0, n - 1, min(SEISMIC_SAMPLE, n)).astype(int)
    scalar = jax.jit(eval_misfit, static_argnames=("nx", "nz", "nt", "seed"))
    ref = np.array([float(scalar(int(xs[i]), nx=nx, nz=nx, nt=nt, seed=seed))
                    for i in pick])
    got = np.asarray(res["misfits"])[pick]
    member_rel = np.abs(got - ref) / np.abs(ref)
    sigma_host = math.fsum(res["misfits"])
    sigma_rel = abs(res["total_misfit"] - sigma_host) / abs(sigma_host)
    report["drift"] = {"sampled_events": int(len(pick)),
                       "max_rel_drift_members": float(member_rel.max()),
                       "sigma": res["total_misfit"],
                       "sigma_rel_drift": sigma_rel}
    if not member_rel.max() <= SEISMIC_RTOL:
        failed.append("misfits disagree with scalar eval_misfit")
    if not sigma_rel <= SIGMA_RTOL:
        failed.append("Σ disagrees with the sum of member misfits")
    report["peak_bytes_in_use"] = peak_bytes()
    report["failed"] = failed
    return report


def sharded_phase(size: Dict[str, int], seed: int = SEED) -> Dict[str, Any]:
    """One AnEn round as a ``dag-shard`` carrier over every device, then the
    same round on ``JaxRTS(devices=[jax.devices()[0]])``; both in this
    process, compared location by location."""
    import jax
    import numpy as np
    from repro import telemetry
    from repro.apps.anen.workflow import _dataset, run_adaptive

    report: Dict[str, Any] = {"phase": "anen-sharded", **size, "k": K}
    failed: List[str] = []
    devices = jax.devices()
    t0 = time.perf_counter()
    data = _dataset(seed, size["ny"], size["nx"], size["n_hist"])
    jax.block_until_ready(data)
    report["setup_s"] = time.perf_counter() - t0
    kw = dict(ny=size["ny"], nx=size["nx"], n_hist=size["n_hist"],
              per_iter=size["per_iter"], max_iters=size["max_iters"],
              n_tasks=size["n_tasks"], slots=SLOTS)
    was_tracing = telemetry.enabled()
    telemetry.enable()
    try:
        with CompileMeter() as meter:
            t0 = time.perf_counter()
            mesh_run = run_adaptive(seed=seed, **kw)
            report["mesh_wall_s"] = time.perf_counter() - t0
        spans = [s for s in telemetry.TRACER.snapshot()
                 if s["name"] == "carrier.dispatch"]
    finally:
        if not was_tracing:
            telemetry.disable()
    report.update(meter.report("mesh"))
    with CompileMeter() as meter:
        t0 = time.perf_counter()
        one_run = run_adaptive(seed=seed, devices=[devices[0]], **kw)
        report["one_device_wall_s"] = time.perf_counter() - t0
    report.update(meter.report("one_device"))
    report["mesh"] = tier_report(mesh_run["rts"])
    report["one_device"] = tier_report(one_run["rts"])
    shards = sorted({s["attrs"].get("mesh_shards") for s in spans
                     if s["attrs"].get("tier") == "dag-shard"})
    report["mesh_shards"] = shards
    for run in (mesh_run, one_run):
        if not run["all_done"] or run["rounds"] != size["max_iters"]:
            failed.append("round did not finish")
    _tier_checks(report["mesh"], "dag-shard", 1, failed)
    _tier_checks(report["one_device"], "dag", 1, failed)
    if not report["mesh"]["fusion_stats"]["sharded_dispatches"] > 0:
        failed.append("no sharded dispatch")
    # a dag-shard carrier exists only over a mesh of distinct devices
    # (fusion.engine.build_mesh refuses duplicates): mesh_shards counts them
    if shards != [len(devices)] or len(devices) < 4:
        failed.append("the mesh does not span four distinct devices")
    if mesh_run["locations"] != one_run["locations"]:
        failed.append("the two runs placed different locations")
    diff = np.abs(np.asarray(mesh_run["values"], np.float32)
                  - np.asarray(one_run["values"], np.float32))
    report["drift"] = {"vs_one_device_max_abs": float(diff.max()),
                       "vs_one_device_agree_share":
                           float((diff <= ANEN_ATOL).mean()),
                       "vs_reference": _anen_agreement(
                           data, mesh_run["locations"], mesh_run["values"],
                           seed)}
    if report["drift"]["vs_one_device_agree_share"] < ANEN_AGREE_SHARE:
        failed.append("sharded values disagree with the one-device run")
    if report["drift"]["vs_reference"]["agree_share"] < ANEN_AGREE_SHARE:
        failed.append("sharded values disagree with compute_analogs")
    report["peak_bytes_in_use"] = peak_bytes()
    report["failed"] = failed
    return report


def _tpu_attached() -> bool:
    """A TPU chip on the PCI bus (JAX's own probe) or an accelerator device
    node: decided without initialising any JAX backend, since a TPU backend
    that finds no chip goes on to look for a cloud metadata server."""
    from jax._src import hardware_utils
    return (hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0
            or bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase and its one-device "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never reports a TPU")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if args.chips > 1 and "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    else:
        os.environ.setdefault("JAX_PLATFORMS", "tpu")
    sys.path.insert(0, str(ROOT / "src"))

    if not args.rehearse and not _tpu_attached():
        print("chip_smoke: no TPU chip attached", file=sys.stderr)
        return 2
    import jax
    try:
        device = device_record()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 2
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: platform {device['platform']!r} is not a TPU",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: {args.chips} devices needed, "
              f"{device['count']} found", file=sys.stderr)
        return 2
    record: Dict[str, Any] = {"phase": "setup", "device": device,
                              "rehearsal": args.rehearse}
    if not args.rehearse:
        from repro.compile_cache import enable_compile_cache
        record["compile_cache"] = enable_compile_cache()
    _emit(record)

    if args.chips > 1:
        phases = [functools.partial(
            sharded_phase, ANEN_SHARDED_TINY if args.rehearse
            else ANEN_SHARDED)]
    else:
        phases = [functools.partial(anen_phase, ANEN_TINY if args.rehearse
                                    else ANEN),
                  functools.partial(seismic_phase, SEISMIC_TINY
                                    if args.rehearse else SEISMIC)]
    failed: List[str] = []
    for phase in phases:
        report = phase()
        _emit(report)
        failed += [f"{report['phase']}: {f}" for f in report["failed"]]
    if failed:
        print("chip_smoke: FAILED: " + "; ".join(failed), file=sys.stderr)
        return 1
    _emit({"ok": True, "device": device_record()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
