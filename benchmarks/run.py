"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

* ``us_per_call`` — the headline per-unit latency of that benchmark cell
  (per-task toolkit overhead for the EnTK benchmarks, per-event/per-location
  time for the use cases).
* ``derived`` — the figure-specific metric(s), ``k=v`` joined by ``;``.

Usage::

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
    PYTHONPATH=src python -m benchmarks.run --only fig6,fig8
    PYTHONPATH=src python -m benchmarks.run --json out.json   # CI artifact
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro import telemetry
from repro.compile_cache import enable_compile_cache

# every emitted row, mirrored as dicts so --json can persist the run as a
# machine-readable artifact (the CI uploads it per-PR)
_ROWS: list = []


def _telemetry_summary() -> dict:
    """Per-kernel dispatch-latency quantiles + jit-cache hit rate for the
    bench that just ran (the registry is zeroed between benches)."""
    kernels = {}
    for k in telemetry.kernels():
        q = telemetry.quantiles(k)
        if not q.get("count"):
            continue
        kernels[k] = {"p50_us": round((q["p50"] or 0.0) * 1e6, 1),
                      "p99_us": round((q["p99"] or 0.0) * 1e6, 1),
                      "count": q["count"]}
    jit = {lbls.get("outcome", "?"): c.value
           for lbls, c in telemetry.REGISTRY.collect(
               "counter", "fusion_jit_cache_total")}
    lookups = jit.get("hit", 0) + jit.get("miss", 0)
    jit["hit_rate"] = (round(jit.get("hit", 0) / lookups, 3)
                       if lookups else None)
    return {"kernels": kernels, "jit_cache": jit}


def _row(name: str, us_per_call: float, **derived) -> None:
    dv = ";".join(f"{k}={v}" for k, v in derived.items())
    _ROWS.append({"name": name, "us_per_call": round(us_per_call, 3),
                  **derived})
    print(f"{name},{us_per_call:.3f},{dv}", flush=True)


def fig6_prototype(quick: bool) -> None:
    from benchmarks import prototype
    n = 50_000 if quick else 200_000
    for r in prototype.run(n_tasks=n):
        _row(f"fig6_prototype_w{r['n_workers']}", r["us_per_task"],
             n_tasks=r["n_tasks"],
             tasks_per_s=round(r["tasks_per_second"]),
             peak_rss_mb=round(r["peak_rss_mb"], 1))


def fig7_overheads(quick: bool) -> None:
    from benchmarks import overheads
    for r in overheads.run():
        n_tasks = 16
        ov = (r["entk_setup_s"] + r["entk_management_s"]
              + r["entk_teardown_s"])
        _row(f"fig7_{r['experiment']}_{r['variant']}",
             ov / n_tasks * 1e6,
             entk_setup_s=round(r["entk_setup_s"], 4),
             entk_mgmt_s=round(r["entk_management_s"], 4),
             entk_teardown_s=round(r["entk_teardown_s"], 4),
             rts_overhead_s=round(r["rts_overhead_s"], 4),
             task_exec_s=round(r.get("task_execution_virtual_s", 0.0), 1),
             makespan_s=round(r.get("virtual_makespan_s", 0.0), 1),
             all_done=r["all_done"])


def sched_scaling(quick: bool) -> None:
    from benchmarks import overheads
    sizes = (100, 1_000) if quick else (100, 1_000, 10_000)
    for r in overheads.scheduler_scaling(sizes, repeats=2 if quick else 3):
        _row(f"sched_{r['n_pipelines']}p", r["mgmt_us_per_task"],
             n_pipelines=r["n_pipelines"],
             marginal_cpu_us_per_task=round(
                 r.get("marginal_cpu_us_per_task", 0.0), 1),
             cpu_s=round(r["cpu_s"], 3),
             mgmt_s=round(r["entk_management_s"], 3),
             wallclock_s=round(r["wallclock_s"], 2),
             all_done=r["all_done"])


def fig8_weak(quick: bool) -> None:
    from benchmarks import scaling
    sizes = (256, 512, 1024) if quick else (512, 1024, 2048, 4096)
    for r in scaling.weak_scaling(sizes):
        _row(f"fig8_weak_{r['n_tasks']}",
             r["entk_management_s"] / r["n_tasks"] * 1e6,
             avg_task_exec_s=round(r["avg_task_execution_s"], 1),
             makespan_s=round(r["virtual_makespan_s"], 1),
             mgmt_s=round(r["entk_management_s"], 3),
             staging_s=round(r["staging_virtual_s"], 1),
             all_done=r["all_done"])


def fig9_strong(quick: bool) -> None:
    from benchmarks import scaling
    n = 2048 if quick else 8192
    slots = (512, 1024) if quick else (1024, 2048, 4096)
    for r in scaling.strong_scaling(n, slots):
        _row(f"fig9_strong_{r['slots']}",
             r["entk_management_s"] / r["n_tasks"] * 1e6,
             n_tasks=r["n_tasks"],
             makespan_s=round(r["virtual_makespan_s"], 1),
             mgmt_s=round(r["entk_management_s"], 3),
             all_done=r["all_done"])


def fig10_seismic(quick: bool) -> None:
    from benchmarks import use_cases
    n = 8 if quick else 16
    cs = (1, 2, 4) if quick else (1, 2, 4, 8)
    for r in use_cases.seismic_concurrency(n, cs,
                                           nx=48 if quick else 64,
                                           nt=80 if quick else 120):
        _row(f"fig10_seismic_c{r['concurrency']}",
             r["wallclock_s"] / r["n_events"] * 1e6,
             task_exec_s=round(r["task_execution_s"], 2),
             wallclock_s=round(r["wallclock_s"], 2),
             attempts=r["attempts"], n_events=r["n_events"],
             failure_rate=r["failure_rate"], all_done=r["all_done"])


def fig11_anen(quick: bool) -> None:
    from benchmarks import use_cases
    t0 = time.time()
    rows = use_cases.anen_compare(
        repeats=2 if quick else 4,
        ny=48 if quick else 64, nx=48 if quick else 64,
        per_iter=30 if quick else 40,
        max_iters=3 if quick else 4,
        n_hist=60 if quick else 100)
    per_loc_us = (time.time() - t0) / max(
        1, sum(r["n_locations"] for r in rows)) * 1e6
    import numpy as np
    aua = [r["aua_rmse"] for r in rows]
    rnd = [r["random_rmse"] for r in rows]
    _row("fig11_anen_adaptive", per_loc_us,
         aua_median_rmse=round(float(np.median(aua)), 4),
         random_median_rmse=round(float(np.median(rnd)), 4),
         aua_wins=sum(r["aua_wins"] for r in rows),
         repeats=len(rows))


def fusion_throughput(quick: bool) -> None:
    from benchmarks import fusion
    rows = fusion.run(quick)
    for r in rows:
        _row(f"fusion_{r['n_members']}", 1e6 / max(1e-9,
                                                   r["fused_tasks_per_s"]),
             n_members=r["n_members"],
             scalar_tasks_per_s=round(r["scalar_tasks_per_s"], 1),
             fused_tasks_per_s=round(r["fused_tasks_per_s"], 1),
             speedup=round(r["speedup"], 2),
             dispatches=r["dispatches"],
             fused_members=r["fused_members"],
             max_drift=r["max_drift"],
             all_done=r["all_done"])
    # the fused path must produce the scalar path's values — a drifting
    # or incomplete run fails the bench (and the CI smoke job) outright
    # (1e-4 relative tolerates reduction reassociation, nothing more)
    bad = [r["n_members"] for r in rows
           if not r["all_done"] or r["max_drift"] > 1e-4]
    if bad:
        raise RuntimeError(f"fusion drift/incomplete at sizes: {bad}")


def chain_throughput(quick: bool) -> None:
    from benchmarks import chain
    rows = chain.run(quick)
    for r in rows:
        _row(f"chain_{r['n_members']}", 1e6 / max(1e-9,
                                                  r["chain_tasks_per_s"]),
             n_members=r["n_members"],
             n_stages=r["n_stages"],
             scalar_s=round(r["scalar_s"], 2),
             staged_s=round(r["staged_s"], 2),
             chain_s=round(r["chain_s"], 2),
             staged_tasks_per_s=round(r["staged_tasks_per_s"], 1),
             chain_tasks_per_s=round(r["chain_tasks_per_s"], 1),
             speedup_vs_staged=round(r["speedup_vs_staged"], 2),
             speedup_vs_scalar=round(r["speedup_vs_scalar"], 2),
             chain_carriers=r["chain_carriers"],
             chain_dispatches=r["chain_dispatches"],
             staged_dispatches=r["staged_dispatches"],
             chain_drift=r["chain_drift"],
             staged_drift=r["staged_drift"],
             all_done=r["all_done"])
    # both fused paths must reproduce the scalar path's values — a drifting
    # or incomplete run fails the bench (and the CI smoke job) outright
    bad = [r["n_members"] for r in rows
           if not r["all_done"] or r["chain_drift"] > 1e-4
           or r["staged_drift"] > 1e-4]
    if bad:
        raise RuntimeError(f"chain drift/incomplete at sizes: {bad}")


def shard_throughput(quick: bool) -> None:
    from benchmarks import shard
    rows = shard.run(quick)
    for r in rows:
        derived = dict(
            n_members=r["n_members"],
            n_devices=r["n_devices"],
            fused_tasks_per_s=round(r["fused_tasks_per_s"], 1),
            shard_tasks_per_s=round(r["shard_tasks_per_s"], 1),
            speedup_vs_fused=round(r["speedup_vs_fused"], 2),
            fused_dispatches=r["fused_dispatches"],
            shard_dispatches=r["shard_dispatches"],
            shard_carriers=r["shard_carriers"],
            max_drift=r["max_drift"],
            all_done=r["all_done"])
        if "scalar_tasks_per_s" in r:
            derived["scalar_tasks_per_s"] = round(r["scalar_tasks_per_s"], 1)
        _row(f"shard_{r['n_members']}",
             1e6 / max(1e-9, r["shard_tasks_per_s"]), **derived)
    # the sharded path must produce the member kernel's values — a drifting
    # or incomplete run fails the bench (and the CI smoke job) outright
    bad = [r["n_members"] for r in rows
           if not r["all_done"] or r["max_drift"] > 1e-4]
    if bad:
        raise RuntimeError(f"shard drift/incomplete at sizes: {bad}")


def dag_throughput(quick: bool) -> None:
    from benchmarks import dag
    rows = dag.run(quick)
    for r in rows:
        _row(f"dag_{r['n_members']}", 1e6 / max(1e-9,
                                                r["dag_tasks_per_s"]),
             n_members=r["n_members"],
             rounds=r["rounds"],
             scalar_s=round(r["scalar_s"], 2),
             staged_s=round(r["staged_s"], 2),
             dag_s=round(r["dag_s"], 2),
             staged_tasks_per_s=round(r["staged_tasks_per_s"], 1),
             dag_tasks_per_s=round(r["dag_tasks_per_s"], 1),
             speedup_vs_staged=round(r["speedup_vs_staged"], 2),
             speedup_vs_scalar=round(r["speedup_vs_scalar"], 2),
             dag_carriers=r["dag_carriers"],
             dag_dispatches=r["dag_dispatches"],
             dispatches_per_round=r["dispatches_per_round"],
             staged_dispatches=r["staged_dispatches"],
             dag_drift=r["dag_drift"],
             staged_drift=r["staged_drift"],
             all_done=r["all_done"])
    # both fused paths must reproduce the scalar path's values, and a
    # whole round must really be ONE composed dispatch — otherwise the
    # bench (and the CI smoke job) fails outright
    bad = [r["n_members"] for r in rows
           if not r["all_done"] or r["dag_drift"] > 1e-4
           or r["staged_drift"] > 1e-4 or r["dispatches_per_round"] > 1]
    if bad:
        raise RuntimeError(f"dag drift/incomplete/multi-dispatch at "
                           f"sizes: {bad}")


def fed_throughput(quick: bool) -> None:
    from benchmarks import federation
    rows = federation.run(quick)
    for r in rows:
        _row(f"fed_{r['config']}", 1e6 / max(1e-9, r["tasks_per_s"]),
             members=r["members"], total_slots=r["total_slots"],
             n_tasks=r["n_tasks"],
             tasks_per_s=round(r["tasks_per_s"], 1),
             speedup_vs_1x4=round(r["speedup_vs_1x4"], 2),
             wallclock_s=round(r["wallclock_s"], 2),
             members_lost=r["members_lost"],
             pilot_lost_requeues=r["pilot_lost_requeues"],
             all_done=r["all_done"])
    # zero-lost-completions is the acceptance bar, not a statistic: a lost
    # task must fail the bench (and with it the CI smoke job)
    incomplete = [r["config"] for r in rows if not r["all_done"]]
    if incomplete:
        raise RuntimeError(f"federation lost completions in: {incomplete}")


def chaos_resilience(quick: bool) -> None:
    from benchmarks import chaos
    rows = chaos.run(quick)
    for r in rows:
        _row(f"chaos_{r['n_members']}",
             1e6 / max(1e-9, r["faulty_tasks_per_s"]),
             n_members=r["n_members"],
             clean_tasks_per_s=r["clean_tasks_per_s"],
             faulty_tasks_per_s=r["faulty_tasks_per_s"],
             clean_s=r["clean_s"], faulty_s=r["faulty_s"],
             recovery_overhead=r["recovery_overhead"],
             retries_charged=r["retries_charged"],
             members_lost=r["members_lost"],
             pilot_lost_requeues=r["pilot_lost_requeues"],
             fault_sites=r["fault_sites"],
             all_done=r["all_done"])
    # zero lost completions under injected faults is the acceptance bar:
    # an incomplete run fails the bench (and the CI smoke job) outright
    if any(not r["all_done"] for r in rows):
        raise RuntimeError("chaos bench lost completions")


def roofline_table(quick: bool) -> None:
    import os
    from benchmarks import roofline
    variants = [("baseline", roofline.DEFAULT_PATH)]
    opt = roofline.DEFAULT_PATH.replace("dryrun.jsonl", "dryrun_opt.jsonl")
    if os.path.exists(opt):
        variants.append(("opt", opt))
    emitted = False
    for tag, path in variants:
        for r in roofline.table(path):
            emitted = True
            if r["status"] != "OK":
                _row(f"roofline_{tag}_{r['arch']}_{r['shape']}", 0.0,
                     status=r["status"])
                continue
            step = max(r["t_compute_s"], r["t_memory_s"],
                       r["t_collective_s"])
            _row(f"roofline_{tag}_{r['arch']}_{r['shape']}", step * 1e6,
                 dominant=r["dominant"],
                 t_comp_ms=round(r["t_compute_s"] * 1e3, 2),
                 t_mem_ms=round(r["t_memory_s"] * 1e3, 2),
                 t_coll_ms=round(r["t_collective_s"] * 1e3, 2),
                 useful=round(r["useful_flops_ratio"] or 0, 3),
                 gib_per_dev=round(r["peak_gib_per_device"], 2))
    if not emitted:
        _row("roofline", 0.0,
             note="no dry-run artifacts; run python -m repro.launch.dryrun")


def serve_throughput(quick: bool) -> None:
    from benchmarks import serve
    r = serve.run(quick)
    _row(f"serve_{r['n_members']}",
         r["concurrent_s"] / r["n_members"] * 1e6,
         n_members=r["n_members"], n_tenants=r["n_tenants"],
         members_per_tenant=r["members_per_tenant"],
         serial_s=r["serial_s"], concurrent_s=r["concurrent_s"],
         serial_tasks_per_s=r["serial_tasks_per_s"],
         serve_tasks_per_s=r["serve_tasks_per_s"],
         speedup_vs_serial=r["speedup_vs_serial"],
         cross_tenant_carriers=r["cross_tenant_carriers"],
         dispatches=r["dispatches"],
         shared_dispatches=r["shared_dispatches"],
         max_drift=r["max_drift"], all_done=r["all_done"])


BENCHES = {
    "fig6": fig6_prototype,
    "fig7": fig7_overheads,
    "sched": sched_scaling,
    "fig8": fig8_weak,
    "fig9": fig9_strong,
    "fig10": fig10_seismic,
    "fig11": fig11_anen,
    "fed": fed_throughput,
    "fusion": fusion_throughput,
    "chain": chain_throughput,
    "shard": shard_throughput,
    "dag": dag_throughput,
    "serve": serve_throughput,
    "chaos": chaos_resilience,
    "roofline": roofline_table,
}

#: repo-root perf-history file: every ``--json`` run of a data-plane bench
#: (fusion/chain) appends its rows here, so throughput is tracked as a
#: trajectory across PRs instead of being overwritten per run
TRAJECTORY = "BENCH_fusion.json"


def _append_trajectory(picks: "list[str]", quick: bool) -> None:
    import os
    rows = [r for r in _ROWS
            if r["name"].startswith(("fusion_", "chain_", "shard_", "dag_",
                                     "serve_", "chaos_"))
            and not r["name"].endswith("_ERROR")]
    if not rows:
        return
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), TRAJECTORY)
    history = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            history = json.load(fh)
        if not isinstance(history, list):
            history = []
    except (OSError, ValueError):
        history = []
    history.append({"benchmarks": picks, "quick": quick,
                    "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                               time.gmtime()),
                    "rows": rows})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(history, fh, indent=2, default=str)
    sys.stderr.write(f"[bench] appended {len(rows)} rows to {path} "
                     f"({len(history)} records)\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the rows as a JSON artifact")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="enable span tracing and export a Chrome-trace "
                         "(Perfetto) JSON of the whole run")
    args = ap.parse_args()
    enable_compile_cache()
    picks = [s for s in args.only.split(",") if s] or list(BENCHES)
    if args.trace:
        telemetry.enable()
    print("name,us_per_call,derived")
    for name in picks:
        # zero the metrics (handles survive) so each bench's telemetry
        # block reflects that bench alone; spans accumulate across the run
        telemetry.REGISTRY.reset()
        first = len(_ROWS)
        t0 = time.time()
        try:
            BENCHES[name](args.quick)
        except Exception as e:  # noqa: BLE001 - report, keep benching
            _row(f"{name}_ERROR", 0.0, error=f"{type(e).__name__}:{e}")
        sys.stderr.write(f"[bench] {name} took {time.time()-t0:.1f}s\n")
        summary = _telemetry_summary()
        if summary["kernels"] or summary["jit_cache"]["hit_rate"] is not None:
            for r in _ROWS[first:]:
                r["telemetry"] = summary
    if args.trace:
        telemetry.export_chrome_trace(args.trace)
        sys.stderr.write(f"[bench] wrote Chrome trace to {args.trace} "
                         f"({len(telemetry.TRACER)} spans, "
                         f"{telemetry.TRACER.dropped_spans} dropped)\n")
    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump({"benchmarks": picks, "quick": args.quick,
                       "rows": _ROWS}, fh, indent=2, default=str)
        sys.stderr.write(f"[bench] wrote {len(_ROWS)} rows to "
                         f"{args.json}\n")
        # data-plane benches additionally append to the repo-root
        # trajectory so perf history survives across PRs
        _append_trajectory(picks, args.quick)
    errors = [r["name"] for r in _ROWS if r["name"].endswith("_ERROR")]
    if errors:
        # a crashed benchmark must fail the harness (the CI smoke job
        # uploads the artifact either way, but goes red)
        sys.stderr.write(f"[bench] FAILED: {errors}\n")
        sys.exit(1)


if __name__ == "__main__":
    main()
