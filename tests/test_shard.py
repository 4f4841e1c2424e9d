"""SPMD sharded data plane: mesh planning, the shard hold buffer, shard-aware
result handles, and the sharded execution path.

In-process tests keep the repo-wide single-CPU-device invariant (see
conftest.py): mesh *planning* and the hold buffer are exercised white-box,
and the sharded *execution* path runs over a 1-device mesh (a degenerate but
real ``shard_map``). True multi-device behaviour — 8-shard dispatches, the
dispatch-count bound, per-shard spill, sharded resume — runs in
subprocesses with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
the same trick the dry-run tests and the shard benchmark use.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro import api
from repro.core import states as st
from repro.core.pst import Task
from repro.fusion import ArrayResult, fusable
from repro.fusion import engine as fengine
from repro.fusion.handles import LazySlice
from repro.fusion.plans import MeshPlan, plan_mesh
from repro.rts.base import ResourceDescription
from repro.rts.jax_rts import JaxRTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@fusable(static_argnames=("scale",))
def k_shard_square(x, scale=1.0):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) * jnp.asarray(x, jnp.float32) * scale


# --------------------------------------------------------------------------- #
# Mesh planning (pure policy)
# --------------------------------------------------------------------------- #

def test_plan_mesh_shapes_and_fallbacks():
    # unknown capacity / degenerate widths: lanes win
    assert plan_mesh(1000, None, 1) is None
    assert plan_mesh(1000, 8, 0) is None
    # fewer than two free devices: no mesh
    assert plan_mesh(1000, 1, 1) is None
    assert plan_mesh(1000, 3, 2) is None
    # below the shard threshold: collective placement would not pay
    assert plan_mesh(63, 8, 1) is None
    assert plan_mesh(63, 8, 1, shard_min_members=64) is None
    p = plan_mesh(64, 8, 1)
    assert p is not None and p.n_shards == 8 and sum(p.batches) == 64
    # oversubscribed logical slots widen lanes, never meshes
    p = plan_mesh(1000, 64, 1, max_devices=8)
    assert p.n_shards == 8
    # member width divides the device count
    p = plan_mesh(1000, 8, 2)
    assert p.n_shards == 4


def test_plan_mesh_dispatch_bound():
    # the whole point: ceil(n / (devices x max_batch)) dispatches, no more
    for n, devices, max_batch in [(100_000, 8, 4096), (10_000, 8, 4096),
                                  (1_000_000, 8, 4096), (500, 4, 64)]:
        p = plan_mesh(n, devices, 1, max_batch=max_batch)
        assert p is not None
        bound = -(-n // (devices * max_batch))
        assert len(p.batches) == bound
        assert sum(p.batches) == n
        # batches are near-equal: no dispatch exceeds the per-shard cap
        assert max(p.batches) <= devices * max_batch
        assert max(p.batches) - min(p.batches) <= 1


def test_mesh_plan_record():
    rec = MeshPlan(n_shards=8, batches=[128, 128]).record()
    assert rec == {"kind": "shard", "mesh": [8, 16], "dispatches": 2}


def test_shard_pad_buckets():
    # per-shard pow2 bucketing up to 512 members/shard ...
    assert fengine.shard_pad(8, 8) == 8
    assert fengine.shard_pad(9, 8) == 16          # ceil(9/8)=2 -> pow2 2
    assert fengine.shard_pad(1000, 8) == 8 * 128  # 125/shard -> 128
    # ... then a flat 256 quantum (pow2 would pad ~2x in dead compute)
    assert fengine.shard_pad(10_000, 8) == 8 * 1280   # 1250 -> 1280, not 2048
    assert fengine.shard_pad(8 * 4096, 8) == 8 * 4096  # exact fit stays exact


def test_build_mesh_rejects_unmeshable_leases():
    import jax
    dev = jax.devices()[0]
    assert fengine.build_mesh([]) is None
    assert fengine.build_mesh(["d0", "d1"]) is None        # placeholder names
    assert fengine.build_mesh([dev, dev]) is None          # oversubscribed
    mesh = fengine.build_mesh([dev])
    assert mesh is not None and mesh.devices.size == 1


# --------------------------------------------------------------------------- #
# Shard hold buffer (white-box: no scheduler, no started pilot)
# --------------------------------------------------------------------------- #

def _held_rts(width_slots=16, max_batch=8):
    """A JaxRTS whose planner sees an 8-device mesh without starting the
    scheduler: the single real CPU device is duplicated to give the hold
    path a multi-device inventory (packing never touches the devices)."""
    import jax
    rts = JaxRTS(devices=[jax.devices()[0]] * 8, fusion_max_batch=max_batch,
                 shard_min_members=8, shard_hold_s=30.0)
    rts._meshable = True
    rts._pool = list(range(width_slots))
    rts._slots_total = width_slots
    return rts


def _group(n, start=0, width=100, key="G"):
    return [Task(name=f"h{start + i}", executable=k_shard_square,
                 kwargs={"x": float(start + i)},
                 tags={"_fusion_group": key, "_fusion_width": width})
            for i in range(n)]


def test_hold_buffer_accumulates_then_emits_bound_quanta():
    rts = _held_rts()   # capacity 8 devices x 8 max_batch = 64
    try:
        # width 100 -> bound ceil(100/64) = 2 dispatches -> 50-member quanta
        out = rts._pack_fusible(_group(30))
        assert out == [] and len(rts._held["G"]) == 30
        assert rts.in_flight() and len(rts.in_flight()) == 30
        out = rts._pack_fusible(_group(30, start=30))
        assert len(out) == 1 and out[0].name.startswith("shard[8x")
        assert len(rts._held["G"]) == 10
        # the final partial arrival completes the width: everything flushes
        out = rts._pack_fusible(_group(40, start=60))
        assert len(out) == 1
        assert "G" not in rts._held and not rts._hold_timers
        assert rts.fusion_stats["shard_carriers"] == 2
    finally:
        rts.stop()


def test_hold_buffer_bypassed_when_mesh_cannot_fire():
    rts = _held_rts()
    try:
        # narrow group (below shard_min_members): packs immediately
        out = rts._pack_fusible(_group(4, width=4))
        assert out and not rts._held
        # opted out of sharding: packs immediately too
        members = _group(8, width=100)
        for t in members:
            t.tags["_no_shard"] = True
        out = rts._pack_fusible(members)
        assert out and not rts._held
    finally:
        rts.stop()


def test_hold_timer_rearms_while_stream_progresses():
    rts = _held_rts()
    try:
        rts._pack_fusible(_group(10))
        assert "G" in rts._hold_timers
        # the idle timer fired while the stream had advanced: re-arm, keep
        # holding (flushing here would fragment the group into tiny packs)
        rts._flush_held("G", seen_at_arm=5)
        assert "G" in rts._held and "G" in rts._hold_timers
        assert len(rts._held["G"]) == 10
        # a busy RTS (earlier quanta queued/running): flushing would only
        # freeze the pack width mid-stream — re-arm instead
        rts._queue.append(Task(name="busy", executable="sleep://0"))
        rts._flush_held("G", seen_at_arm=10)
        assert "G" in rts._held and "G" in rts._hold_timers
        rts._queue.clear()
        # no progress since arming: the stream stalled — flush what we have
        rts._flush_held("G", seen_at_arm=10)
        assert "G" not in rts._held
        assert rts.fusion_stats["shard_carriers"] == 1  # 10 >= shard_min
        assert len(rts._queue) == 1                     # flushed to the queue
    finally:
        rts.stop()


def test_hold_idle_flush_fires_end_to_end():
    # black-box: a partial group whose stream stalls must still execute
    # once shard_hold_s elapses (the width hint overstates on resume)
    rts = _held_rts()
    rts.shard_hold_s = 0.05
    try:
        out = rts._pack_fusible(_group(70))     # one 50-quantum emitted ...
        assert len(out) == 1 and len(rts._held["G"]) == 20
        deadline = time.time() + 5.0
        while rts._held and time.time() < deadline:
            time.sleep(0.01)
        assert not rts._held                    # ... the stalled 20 flushed
    finally:
        rts.stop()


def test_hold_cancel_drops_members():
    rts = _held_rts()
    try:
        members = _group(10)
        rts._pack_fusible(members)
        rts.cancel([m.uid for m in members[:4]])
        assert len(rts._held["G"]) == 6
        rts.cancel([m.uid for m in members[4:]])
        assert "G" not in rts._held and not rts._hold_timers
    finally:
        rts.stop()


def test_planned_group_slots_charges_whole_mesh():
    rts = _held_rts()
    try:
        # a shardable group occupies the whole mesh: the Emgr must charge
        # all 8 device-widths, not the historical single member width
        assert rts.planned_group_slots(100, 1) == 8
        # below the shard threshold: the micro-batch charge is unchanged
        assert rts.planned_group_slots(4, 1) == 1
    finally:
        rts.stop()


# --------------------------------------------------------------------------- #
# Result handles (satellite: repeated materialization must not re-gather)
# --------------------------------------------------------------------------- #

def test_array_result_host_view_is_cached():
    import jax.numpy as jnp
    h = ArrayResult(jnp.arange(6, dtype=jnp.float32))
    first = np.asarray(h)
    assert np.asarray(h) is first          # one gather, N consumers
    assert np.array_equal(first, np.arange(6, dtype=np.float32))


def test_lazy_slice_materializes_once_and_drops_parent():
    import jax.numpy as jnp
    parent = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    s = LazySlice(parent, 2)
    v = s.value
    assert s.value is v                    # sliced once, cached
    assert s._parent is None               # no longer pins the whole batch
    first = np.asarray(s)
    assert np.asarray(s) is first          # host view cached too
    assert np.array_equal(first, np.asarray(parent)[2])


# --------------------------------------------------------------------------- #
# Sharded execution over a 1-device mesh (in-process: a real shard_map)
# --------------------------------------------------------------------------- #

def _forced_mesh_factory(holder):
    """A JaxRTS on the real single CPU device whose planner is forced to
    produce a 1-device mesh for any group >= 4 members — the degenerate
    mesh runs the full sharded code path (NamedSharding placement,
    shard_map dispatch, shard-aware fan-out) in-process."""
    def factory():
        rts = JaxRTS(slot_oversubscribe=4)
        rts._plan_mesh = (lambda n, free, ms, tags:
                          MeshPlan(n_shards=1, batches=[n]) if n >= 4
                          else None)
        holder["rts"] = rts
        return rts
    return factory


def test_sharded_one_device_mesh_matches_scalar():
    def run(shard):
        ens = api.ensemble(k_shard_square,
                           over=[{"x": float(i), "scale": 2.0}
                                 for i in range(8)],
                           name="sm", fuse=shard)
        holder = {}
        factory = (_forced_mesh_factory(holder) if shard
                   else lambda: JaxRTS(slot_oversubscribe=4))
        res = api.run(ens, resources=ResourceDescription(slots=4),
                      rts_factory=factory, timeout=60)
        states = dict(res.task_states)
        vals = [float(np.asarray(s.out.result())) for s in ens.specs]
        stats = dict(holder["rts"].fusion_stats) if holder else {}
        res.close()
        return states, vals, stats

    s_states, s_vals, _ = run(shard=False)
    m_states, m_vals, m_stats = run(shard=True)
    assert s_states == m_states
    assert all(v == st.DONE for v in m_states.values())
    assert s_vals == m_vals            # bit-identical member results
    assert m_stats["sharded_dispatches"] > 0
    assert m_stats["shard_carriers"] > 0


def test_sharded_dispatch_failure_degrades_not_fails(monkeypatch):
    # an exception inside the sharded dispatch (here: placement) must not
    # fail the members — the carrier degrades to the micro-batch ladder
    def boom(self, mesh, placed=None):
        raise RuntimeError("injected placement failure")
    monkeypatch.setattr(fengine.CarrierExecution, "_place", boom)
    ens = api.ensemble(k_shard_square,
                       over=[{"x": float(i)} for i in range(8)], name="dg")
    holder = {}
    res = api.run(ens, resources=ResourceDescription(slots=4),
                  rts_factory=_forced_mesh_factory(holder), timeout=60)
    assert all(v == st.DONE for v in res.task_states.values())
    vals = [float(np.asarray(s.out.result())) for s in ens.specs]
    assert vals == [float(i * i) for i in range(8)]
    assert holder["rts"].fusion_stats["sharded_dispatches"] == 0
    res.close()


# --------------------------------------------------------------------------- #
# Multi-device behaviour (subprocess: 8 forced host devices)
# --------------------------------------------------------------------------- #

def _run_subprocess(source, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(source)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_run_matches_scalar_and_meets_dispatch_bound():
    out = _run_subprocess("""
        import json
        import numpy as np
        from repro import api
        from repro.fusion import fusable
        from repro.rts.base import ResourceDescription
        from repro.rts.jax_rts import JaxRTS

        @fusable(static_argnames=("scale",))
        def kern(x, scale=1.0):
            import jax.numpy as jnp
            x = jnp.asarray(x, jnp.float32)
            return x * x * scale

        N = 512
        over = [{"x": float(i % 97), "scale": 2.0} for i in range(N)]

        def run(shard, max_batch=16):
            holder = {}
            def factory():
                holder["rts"] = JaxRTS(slot_oversubscribe=16,
                                       fusion_max_batch=max_batch,
                                       shard=shard)
                return holder["rts"]
            ens = api.ensemble(kern, over=over, name="e", fuse=shard)
            res = api.run(ens, resources=ResourceDescription(slots=16),
                          rts_factory=factory, shard=shard, timeout=240)
            vals = [float(np.asarray(s.out.result())) for s in ens.specs]
            stats = dict(holder["rts"].fusion_stats)
            all_done = res.all_done
            res.close()
            return vals, stats, all_done

        s_vals, _, s_done = run(shard=False)
        m_vals, stats, m_done = run(shard=True)
        drift = max(abs(a - b) / max(abs(a), 1e-12)
                    for a, b in zip(s_vals, m_vals))
        bound = -(-N // (8 * 16))    # ceil(N / (devices x max_batch))
        print(json.dumps({
            "all_done": bool(s_done and m_done), "drift": drift,
            "sharded_dispatches": stats["sharded_dispatches"],
            "shard_carriers": stats["shard_carriers"], "bound": bound}))
    """)
    assert out["all_done"]
    assert out["drift"] <= 1e-4
    assert out["sharded_dispatches"] >= 1
    # the acceptance bound: the whole group in at most
    # ceil(n / (devices x max_batch)) sharded dispatches
    assert out["sharded_dispatches"] <= out["bound"]


def test_sharded_journal_plan_and_resume_reruns_only_failures():
    out = _run_subprocess("""
        import json
        import numpy as np
        from repro import api
        from repro.fusion import fusable
        from repro.rts.base import ResourceDescription
        from repro.rts.jax_rts import JaxRTS

        CALLS = [0]

        @fusable()
        def kern(xs, poison=0.0):
            CALLS[0] += 1
            import jax.numpy as jnp
            return jnp.asarray(xs, jnp.float32).sum() + poison

        N, BAD = 128, {3, 77}
        journal = "/tmp/shard_resume_journal.jsonl"
        import os
        for p in (journal,):
            if os.path.exists(p):
                os.remove(p)

        def build(poisoned):
            return api.ensemble(
                kern, over=[{"xs": [float(i)] * 3,
                             "poison": float("nan") if i in poisoned else 0.0}
                            for i in range(N)], name="pr")

        def factory():
            return JaxRTS(slot_oversubscribe=16, fusion_max_batch=16)

        res = api.run(build(BAD), resources=ResourceDescription(slots=16),
                      rts_factory=factory, journal_path=journal, timeout=240)
        states = dict(res.task_states)
        res.close()

        # pull the journaled plan off a DONE member record
        plans = []
        with open(journal) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("to") == "DONE" and rec.get("plan"):
                    plans.append(rec["plan"])

        CALLS[0] = 0
        holder = {}
        def factory2():
            holder["rts"] = JaxRTS(slot_oversubscribe=16,
                                   fusion_max_batch=16)
            return holder["rts"]
        ens2 = build(set())
        res2 = api.run(ens2, resources=ResourceDescription(slots=16),
                       rts_factory=factory2, journal_path=journal,
                       resume=True, timeout=240)
        vals_ok = all(
            np.allclose(np.asarray(ens2.specs[i].out.result()), 3.0 * i)
            for i in range(N))
        print(json.dumps({
            "failed_first": sorted(int(k[3:]) for k, v in states.items()
                                   if v == "FAILED"),
            "done_first": sum(v == "DONE" for v in states.values()),
            "resume_all_done": res2.all_done,
            "resume_calls": CALLS[0],
            "resume_sharded": holder["rts"].fusion_stats[
                "sharded_dispatches"],
            "vals_ok": bool(vals_ok),
            "shard_plans": sum(p.get("kind") == "shard" for p in plans),
            "n_plans": len(plans)}))
        res2.close()
    """)
    # session 1: the two poisoned members failed inside sharded dispatches,
    # everyone else is DONE with a {"kind": "shard"} plan on the record
    assert out["failed_first"] == [3, 77]
    assert out["done_first"] == 126
    assert out["shard_plans"] == out["n_plans"] and out["n_plans"] == 126
    # session 2: only the 2 failures re-run (scalar: below every threshold)
    assert out["resume_all_done"] and out["vals_ok"]
    assert out["resume_calls"] == 2
    assert out["resume_sharded"] == 0


def test_sharded_spill_roundtrips_per_shard():
    out = _run_subprocess("""
        import json, os, tempfile
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.fusion.handles import ArrayResult
        from repro.core.results import decode_journal_value

        mesh = Mesh(np.array(jax.devices(), dtype=object), ("m",))
        value = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
        sharded = jax.device_put(value, NamedSharding(mesh, P("m")))
        d = tempfile.mkdtemp()
        rec = ArrayResult(sharded).to_journal(d)
        back = decode_journal_value(rec)
        ok_roundtrip = bool(np.array_equal(np.asarray(back),
                                           np.asarray(value)))

        # corruption of ONE shard is detected, not silently served
        first = rec["shards"][0]["path"]
        with open(first, "r+b") as fh:
            fh.seek(0)
            fh.write(b"xx")
        try:
            np.asarray(decode_journal_value(rec))
            tamper_caught = False
        except Exception:
            tamper_caught = True
        print(json.dumps({
            "codec": rec["__codec__"], "n_shards": len(rec["shards"]),
            "rows": [s["rows"] for s in rec["shards"]],
            "distinct_files": len({s["path"] for s in rec["shards"]}),
            "ok_roundtrip": ok_roundtrip, "tamper_caught": tamper_caught}))
    """)
    assert out["codec"] == "sharded_array"
    assert out["n_shards"] == 8
    assert out["rows"] == [2] * 8
    assert out["distinct_files"] == 8      # content-addressed per shard
    assert out["ok_roundtrip"] and out["tamper_caught"]


def test_micro_batch_lanes_run_on_their_leased_devices():
    # without a mesh, each micro-batch carrier places its stacked inputs on
    # its own leased device; a downstream stage then stacks member values
    # that live on different devices
    out = _run_subprocess("""
        import json
        import numpy as np
        from repro import api
        from repro.fusion import fusable
        from repro.rts.base import ResourceDescription
        from repro.rts.jax_rts import JaxRTS

        @fusable
        def kvec(x):
            import jax.numpy as jnp
            return jnp.full((4,), x, jnp.float32)

        @fusable
        def kdouble(v):
            import jax.numpy as jnp
            return jnp.asarray(v) * 2.0

        holder = {}
        def factory():
            holder["rts"] = JaxRTS(slot_oversubscribe=1, shard=False)
            return holder["rts"]
        first = api.ensemble(kvec, over=[{"x": float(i)} for i in range(64)],
                             name="v")
        second = first.then(kdouble, name="d")
        res = api.run(second, resources=ResourceDescription(slots=8),
                      rts_factory=factory, chain=False, shard=False,
                      timeout=240)
        devices = {d.id for s in first.specs
                   for d in s.out.result().value.devices()}
        vals = [float(np.asarray(s.out.result())[0]) for s in second.specs]
        stats = dict(holder["rts"].fusion_stats)
        print(json.dumps({
            "all_done": res.all_done, "devices": len(devices),
            "vals_ok": vals == [2.0 * i for i in range(64)],
            "dispatches": stats["dispatches"],
            "scalar_fallback": stats["scalar_fallback"],
            "degraded": stats["degraded"]}))
        res.close()
    """)
    assert out["all_done"] and out["vals_ok"]
    assert out["devices"] > 1
    assert out["dispatches"] > 2
    assert out["scalar_fallback"] == 0 and out["degraded"] == 0
