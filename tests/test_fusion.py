"""Fusion engine tests: group keys, planning, batched execution semantics,
Emgr group hand-off, JaxRTS carrier leases, federation failover and journal
resume of partially-failed batches."""

import threading
import time

import numpy as np
import pytest

from repro import api
from repro.core import AppManager
from repro.core import states as st
from repro.core.pst import Task
from repro.core.results import decode_journal_value
from repro.fusion import (ArrayResult, fusable, fusable_reduction,
                          fusion_group_key, plan_group)
from repro.fusion import engine as fengine
from repro.rts.base import RequeueTask, ResourceDescription
from repro.rts.jax_rts import CARRIERS_TOTAL, JaxRTS


# --------------------------------------------------------------------------- #
# Kernels used across the tests (module-level: resume-stable registration)
# --------------------------------------------------------------------------- #

@fusable(static_argnames=("scale",))
def k_square(x, scale=1.0):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) * jnp.asarray(x, jnp.float32) * scale


@fusable(static_argnames=("scale",), pad_argnames=("xs",))
def k_rowsum(xs, poison=0.0, scale=1.0):
    import jax.numpy as jnp
    return jnp.asarray(xs, jnp.float32).sum(axis=1) * scale + poison


@fusable()
def k_touchy(x):
    # float() on a tracer raises under vmap (the whole batch), but is fine
    # scalar — exercising the engine's degrade-to-scalar isolation
    if float(x) >= 100.0:
        raise ValueError("bad member")
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) + 1.0


def plain_member(x):
    return x + 1


# --------------------------------------------------------------------------- #
# Group keys / API tagging
# --------------------------------------------------------------------------- #

def test_group_keys_and_opt_out():
    pts = [{"x": float(i), "scale": 2.0} for i in range(4)]
    keys = {fusion_group_key(k_square, p, slots=1, backend=None)
            for p in pts}
    assert len(keys) == 1 and None not in keys
    # statics / placement / width changes split the group
    assert fusion_group_key(k_square, {"x": 1.0, "scale": 3.0}) not in keys
    assert fusion_group_key(k_square, pts[0], slots=2) not in keys
    assert fusion_group_key(k_square, pts[0], backend="acc") not in keys
    # unmarked callables never fuse
    assert fusion_group_key(plain_member, {"x": 1}) is None


def test_ensemble_tags_members_and_fuse_false_opts_out():
    ens = api.ensemble(k_square, over=[{"x": float(i)} for i in range(4)],
                       name="e1")
    keys = {s.fusion_group for s in ens.specs}
    assert len(keys) == 1 and None not in keys
    compiled = api.compile(ens, name="wf-tag")
    tags = [t.tags.get("_fusion_group")
            for p in compiled for s in p.stages for t in s.tasks]
    assert len(set(tags)) == 1 and tags[0] is not None

    off = api.ensemble(k_square, over=[{"x": 1.0}, {"x": 2.0}],
                       name="e2", fuse=False)
    assert all(s.fusion_group is None for s in off.specs)


# --------------------------------------------------------------------------- #
# Planning (cost model + adaptive micro-batching)
# --------------------------------------------------------------------------- #

def test_plan_group_cost_model_and_lanes():
    # below threshold: everything scalar (the fallback the issue demands)
    p = plan_group(3, free_slots=8, member_slots=1)
    assert p.batches == [] and p.scalar == 3
    # one lane per free member-width slot
    p = plan_group(100, free_slots=4, member_slots=1)
    assert len(p.batches) == 4 and sum(p.batches) == 100 and p.scalar == 0
    # lanes never split below the fuse threshold
    p = plan_group(8, free_slots=8, member_slots=1)
    assert all(b >= 4 for b in p.batches)
    # member width divides the lane count
    p = plan_group(64, free_slots=8, member_slots=4)
    assert len(p.batches) == 2
    # max_batch bounds any single dispatch
    p = plan_group(100, free_slots=1, member_slots=1, max_batch=30)
    assert max(p.batches) <= 30 and sum(p.batches) == 100
    # unknown capacity: a single lane
    p = plan_group(10, free_slots=None, member_slots=1)
    assert p.batches == [10]


# --------------------------------------------------------------------------- #
# Engine semantics (direct, no scheduler)
# --------------------------------------------------------------------------- #

def _collect():
    done = []
    return done, done.append


def test_engine_pads_trims_and_isolates_nonfinite():
    tasks = []
    for i in range(6):
        n = 2 + (i % 3)
        tasks.append(Task(name=f"m{i}", executable=k_rowsum,
                          kwargs={"xs": [[float(i), 1.0]] * n,
                                  "poison": float("nan") if i == 4 else 0.0,
                                  "scale": 1.0}))
    done, deliver = _collect()
    stats = fengine.execute_fused(tasks, ["d0"], threading.Event(), deliver)
    assert stats["fused"] == 5 and stats["failed"] == 1
    by_uid = {c.uid: c for c in done}
    assert len(by_uid) == 6
    for i, t in enumerate(tasks):
        c = by_uid[t.uid]
        if i == 4:
            assert c.exit_code == 1 and "non-finite" in c.exception
            continue
        assert c.exit_code == 0
        vals = np.asarray(c.result)
        assert vals.shape == (2 + (i % 3),)     # padded rows trimmed back
        assert np.allclose(vals, float(i) + 1.0)
        assert isinstance(c.result, ArrayResult)  # device-resident handle


def test_engine_exception_degrades_to_scalar_isolation():
    tasks = [Task(name=f"t{i}", executable=k_touchy, kwargs={"x": float(x)})
             for i, x in enumerate([1.0, 100.0, 2.0, 3.0])]
    done, deliver = _collect()
    stats = fengine.execute_fused(tasks, ["d0"], threading.Event(), deliver)
    assert stats["scalar_fallback"] == 3 and stats["failed"] == 1
    by_name = {c.uid: c for c in done}
    codes = [by_name[t.uid].exit_code for t in tasks]
    assert codes == [0, 1, 0, 0]        # only the culpable member fails
    assert "bad member" in by_name[tasks[1].uid].exception


@fusable(shared_argnames=("model",))
def k_shared(x, model=None):
    import jax.numpy as jnp
    return (jnp.asarray(model, jnp.float32) * x).sum()


def test_engine_rejects_mismatched_shared_args():
    """The group key cannot see shared VALUES; two ensembles with equal
    keys but different shared arrays must not silently compute against
    the first member's array — the engine degrades to scalar execution,
    where every member uses its own."""
    m1 = np.ones(4, np.float32)
    m2 = np.full(4, 3.0, np.float32)
    tasks = [Task(name=f"sh{i}", executable=k_shared,
                  kwargs={"x": float(i + 1), "model": m1 if i < 2 else m2})
             for i in range(4)]
    done, deliver = _collect()
    stats = fengine.execute_fused(tasks, ["d0"], threading.Event(), deliver)
    assert stats["scalar_fallback"] == 4 and stats["fused"] == 0
    by_uid = {c.uid: c for c in done}
    vals = [float(np.asarray(by_uid[t.uid].result)) for t in tasks]
    assert vals == [4.0, 8.0, 36.0, 48.0]   # each member's OWN model


def test_engine_honours_fault_injector_per_member():
    tasks = [Task(name=f"fi{i}", executable=k_square,
                  kwargs={"x": float(i), "scale": 1.0}) for i in range(5)]
    done, deliver = _collect()
    stats = fengine.execute_fused(
        tasks, ["d0"], threading.Event(), deliver,
        fault_injector=lambda t: t.name == "fi2")
    assert stats["failed"] == 1 and stats["fused"] == 4
    by_uid = {c.uid: c for c in done}
    assert by_uid[tasks[2].uid].exception == "injected fault"


# --------------------------------------------------------------------------- #
# Emgr: whole-group hand-off, charged once
# --------------------------------------------------------------------------- #

def _emgr_with_backlog(tasks):
    from repro.core.broker import Broker
    from repro.core.execmanager import ExecManager
    from repro.core.profiler import Profiler
    from repro.core.pst import WorkflowIndex
    from repro.core.state_service import StateService
    broker = Broker()
    broker.declare("pending")
    emgr = ExecManager(broker, StateService(broker), Profiler(),
                       lambda: None, ResourceDescription(slots=4),
                       WorkflowIndex())
    for t in tasks:
        emgr._backlog.setdefault(t.slots, __import__("collections").deque()
                                 ).append((next(emgr._backlog_seq), t))
        emgr._backlog_uids.add(t.uid)
    return emgr


def test_emgr_takes_whole_group_charging_batch_once():
    group = [Task(name=f"g{i}", executable="sleep://0",
                  tags={"_fusion_group": "K"}) for i in range(10)]
    emgr = _emgr_with_backlog(group)
    batch = emgr._pick_batch_locked(free=1, fusion=True)
    assert [t.name for t in batch] == [t.name for t in group]
    assert emgr.n_backlogged() == 0


def test_emgr_without_fusion_charges_per_member():
    group = [Task(name=f"s{i}", executable="sleep://0",
                  tags={"_fusion_group": "K"}) for i in range(10)]
    emgr = _emgr_with_backlog(group)
    batch = emgr._pick_batch_locked(free=2, fusion=False)
    assert len(batch) == 2      # the pre-fusion behaviour, unchanged
    assert emgr.n_backlogged() == 8


def test_emgr_never_pins_group_onto_scalar_federation_member():
    """A fused group landing on a member whose runtime does NOT batch
    (a scalar LocalRTS in a mixed fleet) must be placed and charged task
    by task — pinning 1000 members there with one slot charged would
    drown the scalar pilot while the fusing member idles."""
    def tagged(n):
        return [Task(name=f"t{i}", executable="sleep://0",
                     tags={"_fusion_group": "G"}) for i in range(n)]

    # the scalar member has the most free slots, so placement prefers it
    slots_map = {"cpu": (3, 4), "acc": (1, 4)}
    emgr = _emgr_with_backlog(tagged(10))
    placements = emgr._pick_batch_federated_locked(
        slots_map, {"cpu", "acc"}, fusing={"acc"})
    per_member = {}
    for name, task in placements:
        per_member.setdefault(name, []).append(task)
    # cpu takes only what its free count affords (charged per task);
    # nothing is pinned there beyond capacity
    assert len(per_member.get("cpu", [])) <= 3
    # while a group landing on the fusing member pins whole
    emgr2 = _emgr_with_backlog(tagged(10))
    placements2 = emgr2._pick_batch_federated_locked(
        {"acc": (2, 4)}, {"acc"}, fusing={"acc"})
    assert len(placements2) == 10 and all(n == "acc"
                                          for n, _ in placements2)


def test_emgr_group_drain_stops_at_other_groups():
    tasks = ([Task(name=f"a{i}", executable="sleep://0",
                   tags={"_fusion_group": "A"}) for i in range(3)]
             + [Task(name=f"b{i}", executable="sleep://0",
                     tags={"_fusion_group": "B"}) for i in range(3)])
    emgr = _emgr_with_backlog(tasks)
    batch = emgr._pick_batch_locked(free=2, fusion=True)
    # group A drains with the first charge, group B with the second
    assert [t.name for t in batch] == ["a0", "a1", "a2", "b0", "b1", "b2"]


# --------------------------------------------------------------------------- #
# JaxRTS: carriers, all-or-nothing group leases, single whole-group requeue
# --------------------------------------------------------------------------- #

def test_group_lease_all_or_nothing():
    rts = JaxRTS(devices=["d0", "d1"])
    rts.start(ResourceDescription(slots=2))
    try:
        carrier = Task(name="car", executable="fused://4", slots=2)
        with rts._pool_lock:
            stolen = rts._pool.pop()
        with pytest.raises(RequeueTask):
            rts._lease(carrier)
        assert rts.lease_requeues == 1
        with rts._pool_lock:
            assert len(rts._pool) == 1    # nothing leaked from the pool
            rts._pool.append(stolen)
    finally:
        rts.stop()


def test_fused_group_requeues_once_and_completes_under_churn():
    """Satellite regression: a fusible group leasing multiple devices must
    not deadlock (or livelock) against RequeueTask churn — the whole group
    requeues once, re-enters at the queue front, and completes when the
    inventory recovers."""
    rts = JaxRTS(devices=["d0", "d1"], fusion_min_batch=2)
    rts._can_start = lambda task: True       # force the race window
    rts.start(ResourceDescription(slots=2))
    done = []
    all_done = threading.Event()
    members = [Task(name=f"w{i}", executable=k_square, slots=2,
                    kwargs={"x": float(i), "scale": 1.0},
                    tags={"_fusion_group": "W"}) for i in range(4)]
    want = {m.uid for m in members}

    def cb(c):
        done.append(c)
        if want <= {d.uid for d in done}:
            all_done.set()
    rts.set_callback(cb)
    with rts._pool_lock:
        stolen = rts._pool.pop()             # inventory goes short
    rts.submit(members)
    deadline = time.monotonic() + 5
    while rts.lease_requeues == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert rts.lease_requeues >= 1
    assert not done                          # no completion was fabricated
    for _ in range(20):                      # sample the churn window
        with rts._lock:
            queued = list(rts._queue)
        # the group requeues as ONE carrier (never one entry per member),
        # re-entering at the head — the queue never balloons with retries
        assert len(queued) <= 1
        assert all(t.uid in rts._fused for t in queued)
        time.sleep(0.005)
    with rts._pool_lock:
        rts._pool.append(stolen)             # inventory recovers
    assert all_done.wait(10)
    rts.stop()
    assert {c.exit_code for c in done} == {0}
    assert len(done) == 4                    # every member exactly once


def test_in_flight_reports_member_uids_not_carriers():
    rts = JaxRTS(devices=["d0"], slot_oversubscribe=2, fusion_min_batch=2)
    rts.start(ResourceDescription(slots=2))
    release = threading.Event()

    def blocker(x):
        release.wait(5)
        return x

    try:
        members = [Task(name=f"b{i}", executable=blocker,
                        kwargs={"x": i}, tags={"_fusion_group": "B"})
                   for i in range(3)]
        rts.submit(members)
        deadline = time.monotonic() + 5
        while not rts.running_since() and time.monotonic() < deadline:
            time.sleep(0.01)
        inflight = set(rts.in_flight())
        assert inflight == {m.uid for m in members}
        # the straggler watchdog reasons about member uids too: a hung
        # batch surfaces as its pending members, never as a carrier
        assert set(rts.running_since()) <= {m.uid for m in members}
        assert rts.running_since()
    finally:
        release.set()
        rts.stop()


# --------------------------------------------------------------------------- #
# End-to-end: zero semantic drift, fused vs scalar
# --------------------------------------------------------------------------- #

def _quickstart(fuse, slots=4):
    ens = api.ensemble(k_square,
                       over=[{"x": float(i), "scale": 2.0}
                             for i in range(12)],
                       name="sq", fuse=fuse)
    total = api.gather(ens, lambda vals: float(
        np.sum([np.asarray(v) for v in vals])), name="total")
    res = api.run(total, resources=ResourceDescription(slots=slots),
                  rts_factory=lambda: JaxRTS(devices=["d0"],
                                             slot_oversubscribe=slots),
                  timeout=60)
    states = dict(res.task_states)
    values = [float(np.asarray(s.out.result())) for s in ens.specs]
    out = (states, values, total.out.result())
    res.close()
    return out


def test_fused_and_scalar_runs_are_semantically_identical():
    s_states, s_vals, s_total = _quickstart(fuse=False)
    f_states, f_vals, f_total = _quickstart(fuse=True)
    assert s_states == f_states
    assert all(v == st.DONE for v in f_states.values())
    assert s_vals == f_vals            # bit-identical member results
    assert s_total == f_total


def test_fused_federation_member_kill_matches_scalar(tmp_path):
    """2-member federation, one member killed mid-run: the fused run loses
    zero completions and terminates in the same PST states with the same
    results as a scalar run of the identical description."""
    def run(fuse):
        ens = api.ensemble(k_square,
                           over=[{"x": float(i), "scale": 3.0}
                                 for i in range(24)],
                           name="fed", fuse=fuse)
        rds = [ResourceDescription(slots=2, extra={"name": "m0"}),
               ResourceDescription(slots=2, extra={"name": "m1"})]
        facts = [lambda: JaxRTS(devices=["d0"], slot_oversubscribe=2,
                                fusion_min_batch=2, fusion_max_batch=4),
                 lambda: JaxRTS(devices=["d0"], slot_oversubscribe=2,
                                fusion_min_batch=2, fusion_max_batch=4)]
        amgr = AppManager(resources=rds, rts_factory=facts,
                          heartbeat_interval=0.1)
        compiled = api.compile(ens, name=f"fedwf-{fuse}")
        amgr.workflow = compiled

        def kill():
            time.sleep(0.15)
            amgr.emgr.rts.members[1].rts.simulate_dead = True
        threading.Thread(target=kill, daemon=True).start()
        amgr.run(timeout=60)
        states = {t.name: t.state for p in amgr.workflow
                  for s in p.stages for t in s.tasks}
        vals = [float(np.asarray(s.out.result())) for s in ens.specs]
        assert amgr.emgr.rts_restarts == 0      # failover, not restart
        compiled.close()
        return states, vals

    s_states, s_vals = run(fuse=False)
    f_states, f_vals = run(fuse=True)
    assert set(s_states.values()) == {st.DONE}
    assert set(f_states.values()) == {st.DONE}   # zero lost completions
    assert s_vals == f_vals


# --------------------------------------------------------------------------- #
# Journal resume of a partially-failed batch
# --------------------------------------------------------------------------- #

K_VECTOR_CALLS = [0]


@fusable(static_argnames=("scale",))
def k_vector(x, poison=0.0, scale=1.0):
    import jax.numpy as jnp
    K_VECTOR_CALLS[0] += 1   # per scalar execution; once per trace fused
    return jnp.full((3,), x * scale, jnp.float32) + poison


def _poison_ensemble(poisoned):
    return api.ensemble(
        k_vector,
        over=[{"x": float(i), "scale": 1.0,
               "poison": float("nan") if i in poisoned else 0.0}
              for i in range(8)],
        name="pv")


def test_resume_reruns_only_failed_batch_members(tmp_path):
    journal = str(tmp_path / "wf.jsonl")
    rts_holder = {}

    def factory():
        rts_holder["rts"] = JaxRTS(devices=["d0"], slot_oversubscribe=4)
        return rts_holder["rts"]

    # run 1: members 2 and 5 blow up (NaN) inside the fused dispatch
    ens = _poison_ensemble({2, 5})
    res = api.run(ens, resources=ResourceDescription(slots=4),
                  rts_factory=factory, journal_path=journal, timeout=60)
    states = res.task_states
    assert states["pv-2"] == st.FAILED and states["pv-5"] == st.FAILED
    assert sum(v == st.DONE for v in states.values()) == 6
    res.close()

    # run 2 (resume): the same description, inputs fixed — only the two
    # failed members execute (as scalar tasks: a 2-member regroup is below
    # the fusion threshold, the cost model's scalar fallback); the six
    # DONE members restore from the journal, their array values coming
    # back through the spill codec
    K_VECTOR_CALLS[0] = 0
    ens2 = _poison_ensemble(set())
    res2 = api.run(ens2, resources=ResourceDescription(slots=4),
                   rts_factory=factory, journal_path=journal, resume=True,
                   timeout=60)
    assert all(v == st.DONE for v in res2.task_states.values())
    assert K_VECTOR_CALLS[0] == 2     # zero re-execution of DONE members
    assert rts_holder["rts"].fusion_stats["dispatches"] == 0
    for i in range(8):
        vals = np.asarray(ens2.specs[i].out.result())
        assert np.allclose(vals, float(i)), (i, vals)
    res2.close()


# --------------------------------------------------------------------------- #
# ArrayResult journal spill codec
# --------------------------------------------------------------------------- #

def test_scalar_path_array_results_spill_and_resume(tmp_path):
    """A fused kernel executed on the SCALAR path (fuse=False) returns a
    bare jax array; the spill plane must journal it too, so resume skips
    the DONE members instead of re-running the whole ensemble."""
    journal = str(tmp_path / "wf.jsonl")

    def build():
        return api.ensemble(
            k_vector, over=[{"x": float(i), "scale": 1.0, "poison": 0.0}
                            for i in range(6)],
            name="sv", fuse=False)

    res = api.run(build(), resources=ResourceDescription(slots=4),
                  rts_factory=lambda: JaxRTS(devices=["d0"],
                                             slot_oversubscribe=4),
                  journal_path=journal, timeout=60)
    assert res.all_done
    res.close()

    K_VECTOR_CALLS[0] = 0
    ens2 = build()
    res2 = api.run(ens2, resources=ResourceDescription(slots=4),
                   rts_factory=lambda: JaxRTS(devices=["d0"],
                                              slot_oversubscribe=4),
                   journal_path=journal, resume=True, timeout=60)
    assert res2.all_done
    assert K_VECTOR_CALLS[0] == 0     # zero re-execution on resume
    for i in range(6):
        assert np.allclose(np.asarray(ens2.specs[i].out.result()), float(i))
    res2.close()


def test_array_result_spill_roundtrip(tmp_path):
    value = np.arange(12, dtype=np.float32).reshape(3, 4)
    rec = ArrayResult(value).to_journal(str(tmp_path / "spill"))
    assert rec["__codec__"] == "fused_array"
    back = decode_journal_value(rec)
    assert isinstance(back, ArrayResult)
    assert np.array_equal(np.asarray(back), value)
    # corruption is detected, not silently served
    import glob
    [path] = glob.glob(str(tmp_path / "spill" / "*.npy"))
    np.save(path, value + 1)
    from repro.core.exceptions import MissingError
    with pytest.raises(MissingError):
        decode_journal_value(rec)


def test_array_result_without_spill_dir_is_omitted():
    assert ArrayResult(np.ones(3)).to_journal(None) is None


# --------------------------------------------------------------------------- #
# Chain fusion: detection, whole-chain hand-off, composed execution
# --------------------------------------------------------------------------- #

CHAIN_TAG = "_fusion_chain"


def _two_link(fuse=True, n=4):
    e0 = api.ensemble(k_square,
                      over=[{"x": float(i), "scale": 2.0} for i in range(n)],
                      name="d0", fuse=fuse)
    return e0.then(k_square, name="d1", fuse=fuse)


def test_chain_detection_tags_and_opt_outs():
    compiled = api.compile(_two_link(), name="wf-ct")
    tags = {t.name: t.tags.get(CHAIN_TAG)
            for p in compiled for s in p.stages for t in s.tasks}
    cids = set()
    for i in range(4):
        t0, t1 = tags[f"d0-{i}"], tags[f"d1-{i}"]
        assert t0 == {"c": t0["c"], "k": 0, "m": i, "n": 2}
        assert t1 == {"c": t0["c"], "k": 1, "m": i, "n": 2, "a": "x"}
        cids.add(t0["c"])
    assert len(cids) == 1

    # chain=False / min_chain opt-outs, and fuse=False (no groups, no chain)
    for kwargs, builder in (
            ({"chain": False}, lambda: _two_link()),
            ({"min_chain": 3}, lambda: _two_link()),
            ({}, lambda: _two_link(fuse=False))):
        compiled = api.compile(builder(), name=f"wf-ct-off-{kwargs}",
                               **kwargs)
        assert all(t.tags.get(CHAIN_TAG) is None
                   for p in compiled for s in p.stages for t in s.tasks)


def test_chain_detection_rejects_non_elementwise_shapes():
    # a member consuming TWO futures is not an elementwise link
    e0 = api.ensemble(k_square, over=[{"x": float(i)} for i in range(4)],
                      name="nc0")
    mixed = api.ensemble(
        k_square, over=[{"x": [e0.specs[i].out, e0.specs[(i + 1) % 4].out]}
                        for i in range(4)], name="nc1")
    compiled = api.compile(mixed, name="wf-ncx")
    assert all(t.tags.get(CHAIN_TAG) is None
               for p in compiled for s in p.stages for t in s.tasks)
    # permuted member alignment breaks the chain too
    e2 = api.ensemble(k_square, over=[{"x": float(i)} for i in range(4)],
                      name="nc2")
    rot = api.ensemble(
        k_square, over=[{"x": e2.specs[(i + 1) % 4].out} for i in range(4)],
        name="nc3")
    compiled = api.compile(rot, name="wf-ncr")
    assert all(t.tags.get(CHAIN_TAG) is None
               for p in compiled for s in p.stages for t in s.tasks)


def test_emgr_holds_incomplete_chain_then_drains_whole_on_one_charge():
    def link(k, m, n=3):
        # "ss" = superstage extent: the WFProcessor stamps it when it
        # co-publishes the chain's stages; only stamped links are held
        return Task(name=f"c{k}m{m}", executable="sleep://0",
                    tags={"_fusion_group": f"G{k}",
                          CHAIN_TAG: {"c": "C", "k": k, "m": m, "n": n,
                                      "ss": n - 1}})

    partial = [link(k, m) for k in range(2) for m in range(4)]
    emgr = _emgr_with_backlog(partial)
    emgr._has_chain_backlog = True
    # links 0-1 present, terminal link 2 still in the queue: hold everything
    assert emgr._pick_batch_locked(free=4, fusion=True) == []
    assert emgr.n_backlogged() == 8
    # the terminal arrives: the WHOLE chain drains on a single slot charge
    import collections
    for m in range(4):
        t = link(2, m)
        emgr._backlog.setdefault(t.slots, collections.deque()).append(
            (next(emgr._backlog_seq), t))
        emgr._backlog_uids.add(t.uid)
    batch = emgr._pick_batch_locked(free=1, fusion=True)
    assert len(batch) == 12 and emgr.n_backlogged() == 0


def test_chain_fused_run_matches_scalar_values_and_states():
    def run(fuse, chain):
        e0 = api.ensemble(k_square,
                          over=[{"x": float(i), "scale": 2.0}
                                for i in range(12)], name="ch0", fuse=fuse)
        e1 = e0.then(k_square, name="ch1", fuse=fuse)
        e2 = e1.then(k_square, name="ch2", fuse=fuse)
        # float64 reduction: the scalar path stores fp32 device scalars,
        # the fused fan-out delivers host floats — both exact images of
        # the same fp32 values, but a naive fp32 np.sum would round them
        # differently at this magnitude
        total = api.gather(e2, lambda vals: float(
            sum(float(np.asarray(v)) for v in vals)), name="chtot")
        holder = {}

        def factory():
            holder["rts"] = JaxRTS(devices=["d0"], slot_oversubscribe=4)
            return holder["rts"]

        res = api.run(total, resources=ResourceDescription(slots=4),
                      rts_factory=factory, chain=chain, timeout=60)
        states = dict(res.task_states)
        vals = [float(np.asarray(s.out.result())) for s in e2.specs]
        stats = dict(holder["rts"].fusion_stats)
        out = (states, vals, total.out.result(), stats)
        res.close()
        return out

    s_states, s_vals, s_total, _ = run(fuse=False, chain=False)
    c_states, c_vals, c_total, c_stats = run(fuse=True, chain=True)
    assert s_states == c_states
    assert all(v == st.DONE for v in c_states.values())
    assert s_vals == c_vals          # bit-identical member results
    assert s_total == c_total
    # and the run really used the chain data plane, not per-stage fusion
    assert c_stats["chain_carriers"] > 0
    assert c_stats["chain_links"] > 0


def test_chain_nonfinite_fails_member_and_downstream_links():
    e0 = api.ensemble(k_vector,
                      over=[{"x": float(i), "scale": 1.0,
                             "poison": float("nan") if i == 2 else 0.0}
                            for i in range(6)], name="pz0")
    e1 = e0.then(k_square, name="pz1", arg="x")
    res = api.run(e1, resources=ResourceDescription(slots=4),
                  rts_factory=lambda: JaxRTS(devices=["d0"],
                                             slot_oversubscribe=4),
                  timeout=60)
    states = res.task_states
    assert states["pz0-2"] == st.FAILED
    assert states["pz1-2"] == st.FAILED   # downstream of the poisoned link
    done = [n for n, v in states.items() if v == st.DONE]
    assert len(done) == 10                # every other member, both links
    for p in res.amgr.workflow:
        for s in p.stages:
            for t in s.tasks:
                if t.name == "pz1-2":
                    assert "upstream chain member failed" in t.exception
    res.close()


def test_chain_exception_degrades_to_per_stage_then_scalar():
    # k_touchy raises under vmap (the composed trace dies), and scalar for
    # x >= 100: the chain must degrade per-stage, then per-member, so only
    # the culpable member (and its downstream link) fails
    e0 = api.ensemble(k_square,
                      over=[{"x": x, "scale": 1.0}
                            for x in (1.0, 10.0, 2.0, 3.0)], name="tc0")
    e1 = e0.then(k_touchy, name="tc1", arg="x")
    e2 = e1.then(k_square, name="tc2", arg="x")
    holder = {}

    def factory():
        holder["rts"] = JaxRTS(devices=["d0"], slot_oversubscribe=4)
        return holder["rts"]

    res = api.run(e2, resources=ResourceDescription(slots=4),
                  rts_factory=factory, timeout=60)
    states = res.task_states
    # member 1: 10^2 = 100 -> k_touchy raises scalar too -> FAILED there,
    # and its tc2 link fails downstream; everyone else completes
    assert states["tc1-1"] == st.FAILED and states["tc2-1"] == st.FAILED
    assert sum(v == st.DONE for v in states.values()) == 10
    assert holder["rts"].fusion_stats["scalar_fallback"] >= 1
    for i, x in enumerate((1.0, 10.0, 2.0, 3.0)):
        if i == 1:
            continue
        got = float(np.asarray(
            [s for p in res.amgr.workflow for st_ in p.stages
             for s in st_.tasks if s.name == f"tc2-{i}"][0].result))
        assert got == (x * x + 1.0) ** 2
    res.close()


def test_chain_fail_stage_finalizes_once_and_never_hangs():
    """on_task_failure='fail_stage' + superstage: the downstream link
    stage is already in flight when the entry stage's failure finalizes
    the pipeline — its later closure must not re-finalize (the state
    machine forbids FAILED->FAILED; pre-fix this killed the Dequeue
    thread and hung the run until timeout)."""
    e0 = api.ensemble(k_vector,
                      over=[{"x": float(i), "scale": 1.0,
                             "poison": float("nan") if i == 1 else 0.0}
                            for i in range(4)], name="fs0")
    e1 = e0.then(k_square, name="fs1")
    compiled = api.compile(e1, name="wf-fs")
    amgr = AppManager(resources=ResourceDescription(slots=4),
                      rts_factory=lambda: JaxRTS(devices=["d0"],
                                                 slot_oversubscribe=4),
                      on_task_failure="fail_stage")
    amgr.workflow = compiled
    amgr.run(timeout=30)          # a hang would raise the timeout error
    assert amgr.wfp.component_errors == []
    states = {t.name: t.state for p in amgr.workflow
              for s in p.stages for t in s.tasks}
    assert states["fs0-1"] == st.FAILED
    assert states["fs0-0"] == st.DONE and states["fs1-0"] == st.DONE
    compiled.close()


def test_chain_upstream_retry_revives_downstream_links():
    """A transient upstream failure with retry budget must not permanently
    fail its downstream chain links: they requeue through the pilot_lost
    channel (no budget charge) and re-run with the upstream retry — the
    outcome the per-stage gated path produces."""
    attempts = {"n": 0}

    def injector(task):
        if task.name == "rt0-2":
            attempts["n"] += 1
            return attempts["n"] == 1   # first attempt only
        return False

    e0 = api.ensemble(k_square,
                      over=[{"x": float(i), "scale": 1.0} for i in range(6)],
                      name="rt0", max_retries=1)
    e1 = e0.then(k_square, name="rt1")   # downstream budget: zero retries
    res = api.run(e1, resources=ResourceDescription(slots=4),
                  rts_factory=lambda: JaxRTS(devices=["d0"],
                                             slot_oversubscribe=4,
                                             fault_injector=injector),
                  timeout=60)
    assert attempts["n"] == 2            # exactly one retry
    assert all(v == st.DONE for v in res.task_states.values())
    for i, s in enumerate(e1.specs):
        assert float(np.asarray(s.out.result())) == float(i) ** 4
    res.close()


@fusable_reduction(kind="sum")
def r_sum(values):
    return float(np.sum([np.asarray(v) for v in values]))


def _tier_workflow(shape, fuse):
    """(run node, specs whose values must match) for one carrier shape: a
    fused group, a 2-link chain, or a gather DAG (ensemble -> reduce)."""
    e0 = api.ensemble(k_square,
                      over=[{"x": float(i), "scale": 1.0} for i in range(8)],
                      name=f"ot{shape}{int(fuse)}", fuse=fuse)
    if shape == "fused":
        return e0, list(e0.specs)
    if shape == "chain":
        e1 = e0.then(k_square, name=f"ot{shape}{int(fuse)}b", fuse=fuse)
        return e1, list(e0.specs) + list(e1.specs)
    total = api.gather(e0, r_sum, name=f"ot{shape}{int(fuse)}r")
    return total, list(e0.specs) + [total]


@pytest.mark.parametrize("shape,tier,links_stat", [
    ("fused", "fused", None),
    ("chain", "chain", "chain_links"),
    ("dag", "dag", "dag_links"),
])
def test_one_executor_runs_every_tier(shape, tier, links_stat):
    """Fused groups, chains and gather DAGs all run through the one
    carrier executor: each carrier counts under the tier its tags imply,
    fans out the matching per-node stat, and delivers member values
    bit-identical to an unfused run."""
    def run(fuse):
        node, specs = _tier_workflow(shape, fuse)
        holder = {}

        def factory():
            holder["rts"] = JaxRTS(devices=["d0"], slot_oversubscribe=4)
            return holder["rts"]

        res = api.run(node, resources=ResourceDescription(slots=4),
                      rts_factory=factory, timeout=60)
        assert all(v == st.DONE for v in res.task_states.values())
        vals = [float(np.asarray(s.out.result())) for s in specs]
        rts = holder["rts"]
        tiers = {labels["tier"]: c.value for labels, c
                 in rts.metrics.collect("counter", CARRIERS_TOTAL)
                 if c.value}
        stats = dict(rts.fusion_stats)
        res.close()
        return vals, tiers, stats

    s_vals, s_tiers, _ = run(fuse=False)
    f_vals, f_tiers, f_stats = run(fuse=True)
    assert s_tiers == {}                  # the unfused run made no carrier
    assert set(f_tiers) == {tier}
    for key in ("chain_links", "dag_links"):
        assert (f_stats[key] > 0) == (key == links_stat), (key, f_stats)
    assert f_stats["fused"] > 0
    assert f_vals == s_vals               # bit-identical member results


def test_running_since_and_cancel_for_undrained_async_carrier():
    """Satellite: an awaited-but-undrained dispatch must surface its member
    uids (straggler speculation keys on them) and stay cancellable without
    leaking its device lease."""
    rts = JaxRTS(devices=["d0"], slot_oversubscribe=2, fusion_min_batch=2)
    rts.start(ResourceDescription(slots=2))
    unplug = threading.Event()

    class _Plug:
        def drain(self, stop_event=None):
            unplug.wait(10)
            return {}

    try:
        # wedge EVERY drainer behind a plug so the real carrier stays
        # dispatched-but-undrained (the plugs are never leased, so their
        # release only touches thread-pool accounting of this throwaway RTS)
        for i in range(rts._n_drainers):
            plug_carrier = Task(name=f"plug{i}", executable="plug://")
            rts._drain_q.put((plug_carrier,
                              type("B", (), {"members": []})(), _Plug()))
        members = [Task(name=f"ac{i}", executable=k_square,
                        kwargs={"x": float(i), "scale": 1.0},
                        tags={"_fusion_group": "AC"}) for i in range(3)]
        rts.submit(members)
        uids = {m.uid for m in members}
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            running = rts.running_since()
            if uids <= set(running):
                break
            time.sleep(0.01)
        # undrained carrier: every member uid visible with an elapsed time
        assert uids <= set(rts.running_since())
        assert uids <= set(rts.in_flight())
        # cancel while undrained: bookkeeping must drain clean afterwards
        rts.cancel(list(uids))
        unplug.set()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with rts._pool_lock:
                leaked = bool(rts._leases)
            with rts._fusion_lock:
                tracked = bool(rts._fused)
            if not leaked and not tracked and rts.free_slots() == 2:
                break
            time.sleep(0.01)
        with rts._pool_lock:
            assert not rts._leases          # no leaked device lease
        assert rts.free_slots() == 2
        with rts._fusion_lock:
            assert not rts._fused and not rts._member_carrier
    finally:
        unplug.set()
        rts.stop()


def test_member_call_cache_unwraps_and_invalidates_on_delivery():
    """Satellite: the kwarg resolve+unwrap is cached per task and dropped
    when the member's completion is delivered (retries re-resolve)."""
    arr = ArrayResult(np.ones(3, np.float32))
    t = Task(name="mc", executable=k_square, kwargs={"x": arr, "scale": 1.0})
    call = fengine.member_call(t)
    assert isinstance(call[2]["x"], np.ndarray)    # handle unwrapped
    assert fengine.member_call(t) is call          # cached
    fengine.drop_member_call(t.uid)
    assert fengine.member_call(t) is not call      # invalidated
    # delivery drops the cache entry (a retry must re-resolve its inputs)
    done, deliver = _collect()
    fengine.execute_fused([t], ["d0"], threading.Event(), deliver)
    assert done[0].exit_code == 0
    with fengine._call_lock:
        assert t.uid not in fengine._call_cache


# --------------------------------------------------------------------------- #
# Pallas AnEn distance kernel
# --------------------------------------------------------------------------- #

def test_pallas_anen_distance_matches_reference():
    import jax.numpy as jnp
    from repro.kernels.anen_distance import anen_distance
    rng = np.random.default_rng(7)
    for (h, v, n) in [(60, 3, 37), (9, 2, 130)]:
        fh = jnp.asarray(rng.standard_normal((h, v, n)), jnp.float32)
        fn = jnp.asarray(rng.standard_normal((v, n)), jnp.float32)
        got = anen_distance(fh, fn, interpret=True)
        ref = jnp.sum((fh - fn[None]) ** 2, axis=1)
        assert got.shape == (h, n)
        assert float(jnp.abs(got - ref).max()) < 1e-4


def test_anen_fused_matches_scalar():
    from repro.apps.anen.workflow import run_adaptive
    kw = dict(ny=20, nx=20, n_hist=30, per_iter=16, max_iters=2,
              n_tasks=4, slots=4, timeout=120)
    fused = run_adaptive(seed=3, **kw)
    scalar = run_adaptive(seed=3, fuse=False, **kw)
    assert fused["all_done"] and scalar["all_done"]
    assert np.allclose(fused["errors"], scalar["errors"], atol=1e-5)
