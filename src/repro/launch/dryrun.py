import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any other import (including
# `from repro...`): JAX locks the device count on first initialization, and
# the production meshes below need 512 placeholder host devices. Only the
# dry-run sets this — smoke tests and benchmarks see the real single device.

"""Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell:

    with mesh:
        lowered = jax.jit(step, in_shardings=…, out_shardings=…,
                          donate_argnums=…).lower(**input_specs(arch))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves the cell fits per-device HBM
        compiled.cost_analysis()     # XLA's own counters (recorded raw)
        analyze(compiled.as_text())  # trip-count-correct roofline terms

Results are appended as JSON-lines to ``results/dryrun.jsonl`` (consumed by
``benchmarks/roofline.py`` and EXPERIMENTS.md).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch dbrx-132b \
        --shape train_4k --multi-pod both
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.models.config import SHAPES, get_config, list_archs
from repro.models import input_specs as ispec
from repro.models import sharding as shd
from repro.models import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze, roofline_terms
from repro.models.pspec_ctx import activation_ctx


def _mesh_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               cfg_overrides: Optional[Dict[str, Any]] = None):
    """Build (lowered, meta) for one cell. Raises on sharding bugs."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    cfg = cfg.replace(kv_repeat=shd.kv_repeat_for(cfg, mesh),
                      **(cfg_overrides or {}))
    specs = ispec.input_specs(cfg, shape)
    p_pspecs = shd.param_specs(cfg, mesh)

    with mesh, activation_ctx(mesh, param_pspecs=p_pspecs):
        if shape.kind == "train":
            state_specs = shd.named(mesh, shd.train_state_specs(cfg, mesh))
            batch_sh = shd.named(mesh, shd.batch_pspecs(cfg, shape, mesh))
            abstract_state = steps_mod.abstract_train_state(cfg)
            fn = steps_mod.make_train_step(cfg)
            metric_specs = jax.tree.map(
                lambda _: shd.named(mesh, jax.sharding.PartitionSpec()),
                {"loss": 0, "ce": 0, "aux": 0, "grad_norm": 0, "lr": 0})
            jitted = jax.jit(
                fn,
                in_shardings=(state_specs, batch_sh),
                out_shardings=(state_specs, metric_specs),
                donate_argnums=(0,))
            lowered = jitted.lower(abstract_state, specs["batch"])
        elif shape.kind == "prefill":
            p_specs = shd.named(mesh, shd.param_specs(cfg, mesh))
            batch_sh = shd.named(mesh, shd.batch_pspecs(cfg, shape, mesh))
            abstract_params = steps_mod.transformer.abstract_params(cfg)
            fn = steps_mod.make_prefill_step(cfg)
            lowered = jax.jit(
                fn, in_shardings=(p_specs, batch_sh)).lower(
                abstract_params, specs["batch"])
        else:  # decode
            p_specs = shd.named(mesh, shd.param_specs(cfg, mesh))
            cache_sh = shd.named(mesh, shd.cache_pspecs(cfg, shape, mesh))
            tok_sh = shd.named(mesh, shd.token_pspec(cfg, shape, mesh))
            abstract_params = steps_mod.transformer.abstract_params(cfg)
            fn = steps_mod.make_decode_step(cfg)
            logits_spec = shd.named(
                mesh, jax.sharding.PartitionSpec(None, "model"))
            jitted = jax.jit(
                fn,
                in_shardings=(p_specs, tok_sh, cache_sh),
                out_shardings=(logits_spec, cache_sh),
                donate_argnums=(2,))
            lowered = jitted.lower(abstract_params, specs["token"],
                                   specs["cache"])
    meta = {"arch": arch, "shape": shape_name,
            "multi_pod": multi_pod, "mesh": dict(mesh.shape),
            "kind": shape.kind, "kv_repeat": cfg.kv_repeat,
            "n_params": cfg.n_params(),
            "n_active_params": cfg.n_active_params()}
    return lowered, meta, mesh, cfg, shape


def compile_cell(arch: str, shape_name: str, multi_pod: bool = False,
                 verbose: bool = True,
                 cfg_overrides: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """Lower + compile one cell and extract all dry-run artifacts."""
    t0 = time.time()
    lowered, meta, mesh, cfg, shape = lower_cell(
        arch, shape_name, multi_pod, cfg_overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = analyze(compiled.as_text())
    n_dev = _mesh_devices(mesh)

    record: Dict[str, Any] = {
        **meta,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.output_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "xla_cost_analysis": {k: cost.get(k) for k in
                              ("flops", "bytes accessed")},
        "per_device": {
            "flops": hlo["flops"],
            "bytes": hlo["bytes"],
            "collective_bytes": hlo["collective_bytes"],
        },
        "collective_detail": hlo["collective_detail"],
        "roofline": roofline_terms(hlo),
        "n_devices": n_dev,
    }
    # MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); D = trained tokens.
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * meta["n_active_params"] * tokens
        # backward≈2× forward already included in the 6·N·D convention
        record["model_flops"] = model_flops
        record["model_flops_per_device"] = model_flops / n_dev
        record["useful_flops_ratio"] = (
            model_flops / n_dev / max(1.0, hlo["flops"]))
    else:
        tokens = (shape.global_batch if shape.kind == "decode"
                  else shape.global_batch * shape.seq_len)
        model_flops = 2.0 * meta["n_active_params"] * tokens
        record["model_flops"] = model_flops
        record["model_flops_per_device"] = model_flops / n_dev
        record["useful_flops_ratio"] = (
            model_flops / n_dev / max(1.0, hlo["flops"]))
    if verbose:
        r = record["roofline"]
        print(f"[dryrun] {arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}: "
              f"compile {t_compile:.1f}s  "
              f"peak/dev {record['memory']['peak_bytes_per_device']/2**30:.2f} GiB  "
              f"t_comp {r['t_compute']*1e3:.2f}ms  "
              f"t_mem {r['t_memory']*1e3:.2f}ms  "
              f"t_coll {r['t_collective']*1e3:.2f}ms  "
              f"dominant={r['dominant']}  "
              f"useful={record['useful_flops_ratio']:.2f}")
    return record


def run_cells(archs, shapes, multi_pod_modes, out_path: str,
              stop_on_error: bool = False) -> int:
    failures = 0
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "a") as fh:
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                if (shape_name == "long_500k" and not cfg.sub_quadratic):
                    rec = {"arch": arch, "shape": shape_name, "ok": None,
                           "skipped": ("full-attention arch: no "
                                       "sub-quadratic path at 524288 ctx")}
                    fh.write(json.dumps(rec) + "\n")
                    fh.flush()
                    print(f"[dryrun] {arch} × {shape_name}: SKIP "
                          f"(full attention; see DESIGN.md)")
                    continue
                for mp in multi_pod_modes:
                    try:
                        rec = compile_cell(arch, shape_name, multi_pod=mp)
                    except Exception as e:  # noqa: BLE001
                        failures += 1
                        rec = {"arch": arch, "shape": shape_name,
                               "multi_pod": mp, "ok": False,
                               "error": f"{type(e).__name__}: {e}"}
                        print(f"[dryrun] {arch} × {shape_name} "
                              f"mp={mp}: FAIL {type(e).__name__}: {e}")
                        if stop_on_error:
                            traceback.print_exc()
                            fh.write(json.dumps(rec) + "\n")
                            return failures
                    fh.write(json.dumps(rec) + "\n")
                    fh.flush()
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--stop-on-error", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    modes = {"single": [False], "multi": [True],
             "both": [False, True]}[args.multi_pod]
    failures = run_cells(archs, shapes, modes, args.out,
                         stop_on_error=args.stop_on_error)
    print(f"[dryrun] done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
