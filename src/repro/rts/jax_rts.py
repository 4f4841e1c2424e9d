"""JaxRTS: executes JAX computations on device slots.

A pilot on a TPU pod is a pool of devices; a task's ``slots`` requirement is
the number of devices its jitted step needs. The JaxRTS extends the LocalRTS
scheduler with a device inventory: when a task starts it is leased a concrete
set of devices, delivered to the task callable through the ``devices=``
keyword (if accepted) so the callable can build its mesh / place its arrays.

Leases are all-or-nothing: with slot-aware Emgr submission the toolkit never
over-submits, so a lease that would come up short is a transient inventory
race (e.g. an elastic resize beyond the physical pool), answered by
re-queueing the task (:class:`~repro.rts.base.RequeueTask`) — never by
silently granting fewer devices than ``task.slots``. A requeued task
re-enters at the *front* of the queue (it held the head when scheduled), so
lease races cannot starve wide work behind a stream of narrow tasks.

Fusion (``repro.fusion``): the JaxRTS advertises :meth:`supports_fusion`.
Submitted tasks that share a ``_fusion_group`` tag are packed into *carrier*
tasks — one per micro-batch, sized adaptively from :meth:`free_slots` by the
:mod:`~repro.fusion.plans` cost model (tiny groups fall back to scalar
execution). A carrier occupies one member's worth of devices
(all-or-nothing, single whole-group requeue on a lease race) and executes
every member in one batched dispatch via :mod:`~repro.fusion.engine`, which
fans the result out as ordinary per-member completions — per-member DONE /
FAILED journal records, retries and resume all behave exactly as if the
members had run scalar.

Chain fusion (PR 5): tasks additionally tagged ``_fusion_chain`` are links
of a cross-stage elementwise chain. The packer re-assembles the links from
the tags (``supports_chain_fusion``) and builds carriers spanning ALL of a
member cohort's links, so one member-width lease runs the whole chain as
composed dispatches with the intermediates never touching the host.
Carrier execution is **asynchronous**: the worker thread stacks and
enqueues the dispatches, then hands the carrier to a small pool of
completion *drainer* threads; a drainer blocks on the device outputs, fans
out the per-stage per-member completions in link order (ordering holds
per carrier — carriers may complete in any relative order), and only then
releases the device lease — so host-side stacking of micro-batch *n+1*
overlaps device compute of micro-batch *n*. An awaited-but-undrained carrier still reports
its member uids through :meth:`running_since` (straggler speculation keeps
firing) and stays cancellable without leaking its lease (the drainer owns
the unlease unconditionally).

SPMD sharding (PR 6): a fusion group (or chain cohort) wide enough to clear
``shard_min_members`` on a multi-device pool is planned as a **mesh shape**
(:func:`~repro.fusion.plans.plan_mesh`) instead of micro-batch lanes: each
sharded carrier takes ONE all-or-nothing lease of ``devices ×
member_slots`` slots and the engine executes the whole batch under
``shard_map`` over a 1-D member-axis mesh — O(10^6) members complete in a
handful of dispatches. ``shard=False`` (or a ``_no_shard`` member tag, see
``api.compile(shard=False)``) opts out; oversubscribed pools never shard
(a mesh needs distinct physical devices). The chosen plan — mesh shape or
lane count — is stamped on every member completion for postmortem
debugging, and :meth:`planned_group_slots` lets the ExecManager charge the
whole mesh when packing its submission backlog.

DAG fusion (PR 7): tasks tagged ``_fusion_dag`` are nodes of a fusable
fan-in/fan-out DAG — ensembles feeding a ``@fusable_reduction`` gather whose
output broadcasts into the next ensemble. The packer re-assembles the nodes
(``supports_dag_fusion``) and builds exactly ONE carrier per DAG arrival:
the reduction consumes every member future, so the round is never scattered
into concurrent lanes. A complete round composes into one device program
(``ensemble → segment-reduce → broadcast → ensemble``; sharded rounds
reduce via ``psum``/``pmax`` across the mesh), while resume fragments and
``dag=False`` run the nodes sequentially inside the same carrier —
preserving ordering, per-member journal records and reduction semantics on
the degrade ladder (DAG → in-carrier sequential → per-stage fused →
scalar). Fused groups, chains and DAGs all run in the engine's one
executor (:class:`~repro.fusion.engine.CarrierExecution`), which reads each
node's role and carried kwarg from its tags.

On this CPU container the inventory is logical (``slot_oversubscribe``
logical slots share the physical CPU device) — the accounting, leasing and
isolation logic is identical to the pod case; only the device objects differ.
"""

from __future__ import annotations

import dataclasses
import inspect
import math
import queue
import threading
import time
import traceback
from typing import Any, Dict, List, Optional, Sequence, Set

from .. import telemetry as tel
from ..core.policies import BreakerBoard
from ..core.pst import Task, resolve_executable
from ..fusion import engine as fusion_engine
from ..fusion.groups import (GROUP_TAG, FusionSpec, fusion_spec,
                             parse_chain_tag, parse_dag_tag)
from ..fusion.plans import (DEFAULT_MAX_BATCH, DEFAULT_MIN_CHAIN,
                            DEFAULT_SHARD_MIN_MEMBERS, MeshPlan, plan_chain,
                            plan_dag, plan_group, plan_mesh)
from ..telemetry import MetricsRegistry
from .base import Pilot, RequeueTask, ResourceDescription, TaskCompletion
from .local import LocalRTS

#: counter families behind the ``fusion_stats`` / ``tenant_stats`` snapshot
#: properties (ISSUE 9 race fix: typed locked counters, not a shared dict)
FUSION_EVENTS = "rts_fusion_events_total"
TENANT_EVENTS = "rts_tenant_events_total"
SERVE_HOLD_EVENTS = "rts_serve_hold_events_total"
SERVE_QUEUE_WAIT = "serve_queue_wait_seconds"
CARRIERS_TOTAL = "rts_carriers_total"

_FUSION_STAT_KEYS = ("fused", "scalar_fallback", "failed", "dispatches",
                     "chain_links", "chain_carriers", "sharded_dispatches",
                     "shard_carriers", "dag_carriers", "dag_links",
                     "cross_tenant_carriers", "degraded")
_TENANT_FIELDS = ("members", "shared_dispatches", "completions")


class _FusedBatch:
    """Carrier-side bookkeeping for one fused micro-batch.

    ``links`` — one aligned task list per chain link (a plain fused group
    is a 1-link chain); for a DAG carrier (``dag=True``) one task list per
    DAG *node* instead, with reduction nodes holding a single reduce task;
    ``members`` — every member task across links; ``pending`` — member
    uids still owing a completion; ``mesh_shards`` — device count of a
    planned SPMD mesh (0 = plain micro-batch carrier); ``plan`` — the
    JSON-able plan record stamped onto member completions.
    """

    __slots__ = ("links", "members", "pending", "compose", "mesh_shards",
                 "plan", "dag")

    def __init__(self, links: List[List[Task]], compose: bool = True,
                 mesh_shards: int = 0,
                 plan: Optional[Dict[str, Any]] = None,
                 dag: bool = False) -> None:
        self.links = links
        self.members = [t for link in links for t in link]
        self.pending: Set[str] = {m.uid for m in self.members}
        self.compose = compose
        self.mesh_shards = mesh_shards
        self.plan = plan
        self.dag = dag


class JaxRTS(LocalRTS):
    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 slot_oversubscribe: int = 1, fusion: bool = True,
                 fusion_min_batch: Optional[int] = None,
                 fusion_max_batch: int = DEFAULT_MAX_BATCH,
                 fusion_min_chain: int = DEFAULT_MIN_CHAIN,
                 dag: bool = True,
                 shard: bool = True,
                 shard_min_members: int = DEFAULT_SHARD_MIN_MEMBERS,
                 shard_hold_s: float = 0.25,
                 serve_hold_s: float = 0.0,
                 breakers: Optional[BreakerBoard] = None,
                 **kwargs: Any) -> None:
        super().__init__(**kwargs)
        if devices is None:
            import jax  # deferred: never force jax init at import time
            devices = jax.devices()
        self._devices = list(devices)
        self._oversubscribe = max(1, slot_oversubscribe)
        self._pool: List[int] = []
        self._leases: Dict[str, List[int]] = {}
        self._pool_lock = threading.Lock()
        self.lease_requeues = 0   # short-lease races answered by requeue
        # -- fusion state ---------------------------------------------------#
        self.fusion = fusion
        self.fusion_min_batch = fusion_min_batch
        self.fusion_max_batch = fusion_max_batch
        self.fusion_min_chain = max(2, fusion_min_chain)
        # dag=False declines DAG *composition* only: DAG-tagged tasks still
        # execute inside one carrier (sequential per-node — the carrier is
        # what orders the reduce after its members), just never as one
        # composed device program
        self.dag = dag
        self.shard = shard
        self.shard_min_members = shard_min_members
        self.shard_hold_s = shard_hold_s
        # serving mode (PR 8): >0 opens a continuous-batching window —
        # fusible groups are parked briefly so same-kernel members from
        # OTHER workflows (the fusion key excludes the namespace) can land
        # in the same carriers. The window is a hard deadline, not an idle
        # timeout: under a steady multi-tenant stream an idle re-arm would
        # never fire and small tenants would starve behind it.
        self.serve_hold_s = serve_hold_s
        self._meshable: Optional[bool] = None   # lazily probed device types
        # -- shard hold buffer ----------------------------------------------#
        # members of a wide group arrive as a stream of partial submissions
        # (the Broker hands the Emgr what the WFP has enqueued so far);
        # packing each partial slice would fragment the group into many
        # small mesh dispatches. Groups whose compile-time width hint
        # (``_fusion_width``) says more members are coming are held here
        # until a full-mesh batch (devices x max_batch) accumulates, the
        # whole group has arrived, or ``shard_hold_s`` elapses — whichever
        # is first. The deadline bounds the latency cost of holding and
        # guarantees progress when the hint overstates (resume re-runs a
        # subset of the original ensemble).
        self._held: Dict[str, List[Task]] = {}
        self._hold_seen: Dict[str, int] = {}
        self._hold_timers: Dict[str, threading.Timer] = {}
        self._hold_arrived: Dict[str, float] = {}   # member uid -> hold t0
        self._hold_lock = threading.Lock()
        self._fusion_lock = threading.Lock()
        self._fused: Dict[str, _FusedBatch] = {}      # carrier uid -> batch
        self._member_carrier: Dict[str, str] = {}     # member uid -> carrier
        self._fused_canceled: Set[str] = set()        # member uids
        # per-instance metrics registry (ISSUE 9). The old ``fusion_stats``
        # and ``tenant_stats`` dicts were incremented from the packer, the
        # carrier workers AND the drainer pool — a classic lost-update race.
        # They are now read-only snapshot PROPERTIES assembled from typed
        # locked counters in this registry; every writer goes through a
        # shared counter handle instead of a plain dict cell.
        self.metrics = MetricsRegistry()
        # -- circuit breakers (chaos plane) ----------------------------------#
        # per-(kernel, tier) breakers over the degrade ladder: a tier that
        # keeps failing is skipped at PACK time (composed → fused → scalar)
        # instead of rediscovered on every dispatch, and re-closes after a
        # probation window through a single half-open probe carrier.
        # Outcomes are recorded by the drainer from each carrier's stats.
        self.breakers = (breakers if breakers is not None
                         else BreakerBoard(registry=self.metrics))
        self._label_cache: Dict[Any, Optional[str]] = {}
        # -- async data plane -------------------------------------------------#
        # dispatched-but-undrained carriers flow through this queue to a
        # small pool of drainer threads, which own unlease + release: the
        # carrier worker returns as soon as the dispatches are enqueued, so
        # the next carrier's host-side stacking overlaps this one's device
        # compute. A pool (not one thread) so a single hung dispatch
        # head-of-line blocks at most one drainer — other carriers keep
        # completing and straggler speculation stays scoped to the members
        # actually stuck. Per-carrier link ordering is preserved (a carrier
        # drains wholly inside one thread).
        self._drain_q: "queue.Queue" = queue.Queue()
        self._drainers: List[threading.Thread] = []
        self._n_drainers = 2

    # -- stats snapshots (registry-backed, read-only) -------------------------#

    @property
    def fusion_stats(self) -> Dict[str, int]:
        """Point-in-time snapshot of the fusion counters (plain dict, same
        keys as ever — benchmarks and tests keep reading it unchanged)."""
        out = {k: 0 for k in _FUSION_STAT_KEYS}
        for labels, c in self.metrics.collect("counter", FUSION_EVENTS):
            out[labels["kind"]] = c.value
        return out

    @property
    def tenant_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-tenant fan-out accounting snapshot: tenant label ->
        ``{"members", "shared_dispatches", "completions"}``. A member's
        tenant label is its ``_tenant`` tag (stamped by the serving layer)
        or, absent that, its workflow namespace."""
        out: Dict[str, Dict[str, int]] = {}
        for labels, c in self.metrics.collect("counter", TENANT_EVENTS):
            ts = out.setdefault(labels["tenant"],
                                {f: 0 for f in _TENANT_FIELDS})
            ts[labels["field"]] = c.value
        return out

    def _fusion_count(self, kind: str, n: int = 1) -> None:
        self.metrics.counter(FUSION_EVENTS, kind=kind).inc(n)

    def _tenant_count(self, tenant: str, field: str, n: int = 1) -> None:
        self.metrics.counter(TENANT_EVENTS, tenant=tenant, field=field).inc(n)

    def start(self, resources: ResourceDescription) -> Pilot:
        n_logical = len(self._devices) * self._oversubscribe
        if resources.slots > n_logical:
            # clamp a COPY to the inventory: the caller's description must
            # not be mutated; the granted count is reported through the
            # returned pilot's description (the Emgr records it from there)
            resources = dataclasses.replace(resources, slots=n_logical,
                                            extra=dict(resources.extra))
        with self._pool_lock:
            self._pool = list(range(n_logical))
            self._leases = {}
        with self._fusion_lock:
            self._fused.clear()
            self._member_carrier.clear()
            self._fused_canceled.clear()
        self._drain_q = queue.Queue()
        pilot = super().start(resources)
        self._drainers = [
            threading.Thread(target=self._drain_loop,
                             name=f"rts-fusion-drainer-{i}", daemon=True)
            for i in range(self._n_drainers)]
        for t in self._drainers:
            t.start()
        return pilot

    def stop(self) -> None:
        super().stop()
        with self._hold_lock:
            for timer in self._hold_timers.values():
                timer.cancel()
            self._hold_timers.clear()
            self._held.clear()
            self._hold_seen.clear()
        for _ in self._drainers:
            self._drain_q.put(None)
        for t in self._drainers:
            t.join(timeout=5.0)
        self._drainers = []
        with self._fusion_lock:
            self._fused.clear()
            self._member_carrier.clear()
            self._fused_canceled.clear()

    def resize(self, slots: int) -> int:
        # never grow past the physical inventory: slots without devices
        # behind them would turn every lease into a requeue storm
        slots = min(slots, len(self._devices) * self._oversubscribe)
        return super().resize(slots)

    def free_slots(self) -> Optional[int]:
        """Devices actually leasable right now (inventory, not arithmetic)."""
        with self._pool_lock:
            return len(self._pool)

    def supports_fusion(self) -> bool:
        return self.fusion

    def planned_group_slots(self, n_members: int, member_slots: int) -> int:
        """Slots the Emgr should charge for one fusible group right now:
        a group wide enough to shard occupies the WHOLE mesh for its
        dispatch, so the Emgr must not pack other work into those slots
        (the micro-batch case keeps the historical one-member charge —
        lanes backfill into genuinely free capacity)."""
        mesh = self._plan_mesh(n_members, self.free_slots(), member_slots,
                               None)
        if mesh is not None:
            return mesh.n_shards * member_slots
        return member_slots

    def supports_chain_fusion(self) -> bool:
        """True when this RTS composes ``_fusion_chain``-tagged stages into
        single multi-link dispatches. The WFProcessor only *superstages*
        (hands a chain's downstream stages off together with the entry
        stage) against an RTS that answers True — everywhere else, stage
        ordering keeps gating submissions exactly as before."""
        return self.fusion

    def supports_dag_fusion(self) -> bool:
        """True when this RTS assembles ``_fusion_dag``-tagged nodes into
        whole-round carriers. The WFProcessor only superstages a fusable
        DAG (ensembles + gather + broadcast consumers in one batch) against
        an RTS that answers True. Note this gates *routing*, not
        composition: ``dag=False`` still routes DAG tasks through a
        carrier (sequential per-node) because the reduce must be ordered
        after its member inputs."""
        return self.fusion

    # -- submission -----------------------------------------------------------#

    def submit(self, tasks: List[Task]) -> None:
        """Reject tasks wider than the whole device inventory immediately
        (they could never start), pack fusible groups into carriers, and
        queue the rest as ordinary scalar tasks."""
        inventory = len(self._devices) * self._oversubscribe
        runnable: List[Task] = []
        for task in tasks:
            if task.slots > inventory:
                now = time.time()
                self._deliver(TaskCompletion(
                    uid=task.uid, exit_code=2,
                    exception=(f"task requires {task.slots} device slots, "
                               f"inventory is {inventory}"),
                    started_at=now, completed_at=now))
            else:
                runnable.append(task)
        if not runnable:
            return
        super().submit(self._pack_fusible(runnable) if self.fusion
                       else runnable)

    def _pack_fusible(self, tasks: List[Task]) -> List[Task]:
        """Group tagged tasks by fusion key; each group becomes carriers
        (micro-batched from the free-device count) plus a scalar remainder
        when the cost model says a batch would be too small to pay off.
        ``_fusion_chain``-tagged tasks are first re-assembled into chain
        carriers spanning every link present in this submission.

        ``free_slots()`` is read ONCE here and threaded through the group
        planners: it takes the pool lock, and a submission can contain many
        groups — the plan should reflect one consistent snapshot of the
        inventory, not a fresh lock round-trip per micro-batch."""
        groups: Dict[str, List[Task]] = {}
        chains: Dict[str, Dict[int, Dict[int, Task]]] = {}  # c->member->link
        dags: Dict[str, Dict[int, Dict[int, Task]]] = {}    # c->node->member
        order: List[Any] = []   # tasks / group keys / chain ids, in order
        for task in tasks:
            dtag = parse_dag_tag(task.tags)
            if dtag is not None:
                # like chains, ALWAYS routed through the assembler — even
                # with the dag knob off, a reduce task must execute inside
                # a carrier that orders it after its members
                per_node = dags.get(dtag["c"])
                if per_node is None:
                    dags[dtag["c"]] = per_node = {}
                    order.append(("dag", dtag["c"]))
                per_node.setdefault(dtag["k"], {})[dtag["m"]] = task
                continue
            chain = parse_chain_tag(task.tags)
            if chain is not None:
                # ALWAYS routed through the assembler — even chains the
                # min_chain policy declines to compose execute inside a
                # carrier (per-stage, link-ordered): superstaged downstream
                # links must never run as free-floating concurrent tasks
                per_member = chains.get(chain["c"])
                if per_member is None:
                    chains[chain["c"]] = per_member = {}
                    order.append(("chain", chain["c"]))
                per_member.setdefault(chain["m"], {})[chain["k"]] = task
                continue
            key = task.tags.get(GROUP_TAG)
            if key is None:
                order.append(task)
                continue
            bucket = groups.get(key)
            if bucket is None:
                groups[key] = bucket = []
                order.append((GROUP_TAG, key))
            bucket.append(task)
        if not groups and not chains and not dags:
            return tasks
        free = self.free_slots()
        out: List[Task] = []
        for entry in order:
            if isinstance(entry, Task):
                out.append(entry)
                continue
            if entry[0] == "dag":
                self._assemble_dag(dags[entry[1]], out, free)
                continue
            if entry[0] == "chain":
                self._assemble_chain(chains[entry[1]], out, free)
                continue
            if (self.serve_hold_s > 0
                    and self._kernel_spec(groups[entry[1]][0]) is not None):
                self._serve_hold(entry[1], groups[entry[1]], out, free)
                continue
            self._pack_or_hold(entry[1], groups[entry[1]], out, free)
        return out

    def _serve_hold(self, key: str, members: List[Task], out: List[Task],
                    free: Optional[int]) -> None:
        """Continuous batching (serving mode): park a fused group so
        key-compatible members from other tenants can join its carriers.

        Reuses the shard-hold buffer (``_held``) so cancellation,
        ``in_flight`` and ``stop`` see held members with no extra plumbing
        — but with different emission rules: capacity-sized batches go out
        immediately (a full batch gains nothing by waiting) and the
        remainder waits for a HARD ``serve_hold_s`` deadline rather than
        an idle re-arm, so a lone tenant's tail is never starved by a busy
        neighbour keeping the stream "active"."""
        capacity = max(1, len(self._devices) * self.fusion_max_batch)
        arm_key = None
        now = time.perf_counter()
        with self._hold_lock:
            opened = key not in self._held
            held = self._held.setdefault(key, [])
            held.extend(members)
            for m in members:
                self._hold_arrived[m.uid] = now
            self._hold_seen[key] = self._hold_seen.get(key, 0) + len(members)
            batches: List[List[Task]] = []
            while len(held) >= capacity:
                batches.append(held[:capacity])
                del held[:capacity]
            if not held:
                self._held.pop(key, None)
                self._hold_seen.pop(key, None)
                timer = self._hold_timers.pop(key, None)
                if timer is not None:
                    timer.cancel()
            elif key not in self._hold_timers:
                arm_key = key   # deadline runs from the FIRST hold
        self.metrics.counter(
            SERVE_HOLD_EVENTS, event="open" if opened else "extend").inc()
        tel.event("serve.hold", "serve", key=key,
                  event="open" if opened else "extend", n=len(members))
        for batch in batches:
            self.metrics.counter(SERVE_HOLD_EVENTS,
                                 event="capacity_flush").inc()
            self._observe_hold_wait(batch)
            self._pack_group(self._interleave_tenants(batch), out, free)
        if arm_key is not None:
            timer = threading.Timer(self.serve_hold_s, self._flush_serve,
                                    args=(arm_key,))
            timer.daemon = True
            with self._hold_lock:
                if arm_key in self._held and arm_key not in self._hold_timers:
                    self._hold_timers[arm_key] = timer
                    timer.start()

    def _flush_serve(self, key: str) -> None:
        """Deadline flush for a serve-held group: pack whatever
        accumulated, unconditionally — no busy/progress re-arm."""
        if self._stop.is_set():
            return
        with self._hold_lock:
            members = self._held.pop(key, None)
            self._hold_seen.pop(key, None)
            self._hold_timers.pop(key, None)
        if not members:
            return
        self.metrics.counter(SERVE_HOLD_EVENTS, event="deadline_flush").inc()
        tel.event("serve.hold", "serve", key=key, event="deadline_flush",
                  n=len(members))
        self._observe_hold_wait(members)
        out: List[Task] = []
        self._pack_group(self._interleave_tenants(members), out,
                         self.free_slots())
        if out:
            super().submit(out)

    def _observe_hold_wait(self, members: List[Task]) -> None:
        """Serve-hold queue wait, per tenant: time from landing in the hold
        buffer to being packed into a carrier."""
        now = time.perf_counter()
        with self._hold_lock:
            waits = [(m, self._hold_arrived.pop(m.uid, None))
                     for m in members]
        for m, t0 in waits:
            if t0 is None:
                continue
            label = m.tags.get("_tenant") or m.tags.get("_wf_ns") or "-"
            self.metrics.histogram(SERVE_QUEUE_WAIT, tenant=label) \
                .observe(now - t0)

    @staticmethod
    def _interleave_tenants(members: List[Task]) -> List[Task]:
        """Round-robin members across tenants before packing.

        A hold accumulates members in arrival order — one tenant's whole
        sweep, then the next — and the planner slices carriers off that
        sequence, which would hand each carrier back to a single tenant.
        Interleaving makes every carrier a cross-tenant mix AND every
        dispatch deliver progress to every waiting tenant (per-member
        order within a tenant is preserved)."""
        by_tenant: Dict[Any, List[Task]] = {}
        for m in members:
            label = m.tags.get("_tenant") or m.tags.get("_wf_ns")
            by_tenant.setdefault(label, []).append(m)
        if len(by_tenant) <= 1:
            return members
        queues = [list(reversed(q)) for q in by_tenant.values()]
        mixed: List[Task] = []
        while queues:
            queues = [q for q in queues if q]
            for q in queues:
                if q:
                    mixed.append(q.pop())
        return mixed

    def _pack_or_hold(self, key: str, members: List[Task], out: List[Task],
                      free: Optional[int]) -> None:
        """Pack a fused group now, or hold a partially-arrived wide one.

        Holding applies only when the mesh planner could fire for the full
        group (shard on, real multi-device inventory, no ``_no_shard``)
        and the compile-time width hint says members beyond this
        submission are still in flight. Full-mesh batches are emitted as
        they fill; the remainder waits for the rest of the group or the
        ``shard_hold_s`` deadline."""
        tags = members[0].tags
        width = int(tags.get("_fusion_width") or 0)
        if (not self.shard or len(self._devices) < 2
                or not self._mesh_capable() or tags.get("_no_shard")
                or width < self.shard_min_members
                or self._kernel_spec(members[0]) is None):
            self._pack_group(members, out, free)
            return
        # emit in equal quanta sized so the whole group needs exactly
        # ceil(width / (devices x max_batch)) dispatches — the planner's
        # dispatch bound — while early quanta still overlap the stream
        capacity = len(self._devices) * self.fusion_max_batch
        target = math.ceil(width / max(1, math.ceil(width / capacity)))
        arm_key = None
        with self._hold_lock:
            held = self._held.setdefault(key, [])
            held.extend(members)
            seen = self._hold_seen.get(key, 0) + len(members)
            self._hold_seen[key] = seen
            batches: List[List[Task]] = []
            while len(held) >= target:
                batches.append(held[:target])
                del held[:target]
            if held and seen >= width:
                batches.append(held[:])   # the whole group has arrived
                del held[:]
            if not held:
                self._held.pop(key, None)
                self._hold_seen.pop(key, None)
                timer = self._hold_timers.pop(key, None)
                if timer is not None:
                    timer.cancel()
            elif key not in self._hold_timers:
                arm_key = key    # idle timer runs from the FIRST hold
        for batch in batches:
            self._pack_group(batch, out, free)
        if arm_key is not None:
            self._arm_hold_timer(arm_key)

    def _arm_hold_timer(self, key: str) -> None:
        """Arm (or re-arm) the inactivity timer for a held group; the seen
        count at arm time lets the flush distinguish a stalled stream from
        one that is still making progress."""
        with self._hold_lock:
            if key not in self._held or key in self._hold_timers:
                return
            timer = threading.Timer(self.shard_hold_s, self._flush_held,
                                    args=(key, self._hold_seen.get(key, 0)))
            timer.daemon = True
            self._hold_timers[key] = timer
        timer.start()

    def _flush_held(self, key: Optional[str] = None,
                    seen_at_arm: Optional[int] = None) -> None:
        """Inactivity flush: pack whatever a held group accumulated.

        ``shard_hold_s`` is an idle timeout, not an absolute deadline —
        while the Emgr is still streaming group members in, the timer
        re-arms instead of fragmenting the hold into undersized packs
        (enqueuing a very wide group takes far longer than the timeout).
        A busy RTS counts as progress too: while carriers are queued or
        running, the stream only looks stalled because the scheduler is
        waiting out this group's own earlier quanta (or the GIL is pinned
        by their stacking) — flushing would just freeze the pack width
        mid-stream, fragmenting the group far past the planner's dispatch
        bound. A partial hold flushes only once the RTS is otherwise idle
        AND the stream made no progress for a full period — i.e. the held
        members are the only work left."""
        busy = False
        if key is not None and seen_at_arm is not None:
            with self._lock:
                busy = bool(self._running) or bool(self._queue)
        with self._hold_lock:
            rearm = False
            if key is not None and seen_at_arm is not None:
                self._hold_timers.pop(key, None)
                rearm = key in self._held and (
                    busy or self._hold_seen.get(key, 0) > seen_at_arm)
            keys = [] if rearm else (
                [key] if key is not None else list(self._held))
            flushed: List[List[Task]] = []
            for k in keys:
                members = self._held.pop(k, None)
                self._hold_seen.pop(k, None)
                timer = self._hold_timers.pop(k, None)
                if timer is not None:
                    timer.cancel()
                if members:
                    flushed.append(members)
        if rearm:
            self._arm_hold_timer(key)
            return
        out: List[Task] = []
        for members in flushed:
            self._pack_group(members, out, self.free_slots())
        if out:
            super().submit(out)

    def _mesh_capable(self) -> bool:
        """True when the inventory is real jax devices (a unit-test pool of
        placeholder names cannot host a Mesh)."""
        if self._meshable is None:
            try:
                import jax
                self._meshable = bool(self._devices) and all(
                    isinstance(d, jax.Device) for d in self._devices)
            except Exception:  # noqa: BLE001 - no jax, no mesh
                self._meshable = False
        return self._meshable

    def _plan_mesh(self, n_members: int, free: Optional[int],
                   member_slots: int,
                   tags: Optional[Dict[str, Any]]) -> Optional[MeshPlan]:
        """Mesh plan for a wide group, or None → micro-batch lanes.

        The free count is clamped to the scheduler's slot total so a mesh
        carrier can never be planned wider than the pilot will ever admit
        (the pool counts logical inventory, which may exceed the pilot),
        and the mesh is capped at the distinct physical device count —
        oversubscribed logical slots widen lanes, never meshes."""
        if not self.shard or not self._mesh_capable():
            return None
        if tags is not None and tags.get("_no_shard"):
            return None
        if free is not None:
            free = min(free, self._slots_total)
        return plan_mesh(n_members, free, member_slots,
                         max_batch=self.fusion_max_batch,
                         shard_min_members=self.shard_min_members,
                         max_devices=len(self._devices))

    def _pack_group(self, members: List[Task], out: List[Task],
                    free: Optional[int]) -> None:
        spec = self._kernel_spec(members[0])
        if spec is None:
            out.extend(members)   # unmarked kernel: never fuse
            return
        label = self._kernel_label_of(members[0])
        if not self.breakers.allow(label, "fused"):
            out.extend(members)   # breaker open: run the ladder's floor
            return
        mesh = self._plan_mesh(len(members), free, members[0].slots,
                               members[0].tags)
        if mesh is not None and not self.breakers.allow(label, "shard"):
            mesh = None           # breaker open: micro-batch lanes instead
        if mesh is not None:
            record = mesh.record()
            idx = 0
            for size in mesh.batches:
                out.append(self._make_carrier(
                    [members[idx:idx + size]], mesh_shards=mesh.n_shards,
                    plan=record))
                idx += size
            return
        min_batch = (spec.min_batch if spec.min_batch is not None
                     else self.fusion_min_batch)
        plan = plan_group(len(members), free,
                          members[0].slots, min_batch=min_batch,
                          max_batch=self.fusion_max_batch)
        idx = 0
        for size in plan.batches:
            out.append(self._make_carrier([members[idx:idx + size]],
                                          plan=plan.record()))
            idx += size
        out.extend(members[idx:])  # below-threshold remainder: scalar

    def _assemble_chain(self, per_member: Dict[int, Dict[int, Task]],
                        out: List[Task], free: Optional[int] = None) -> None:
        """Build chain carriers from the links present in this submission.

        Members are grouped into *cohorts* by the link range they submit
        (a fresh run submits every member at link 0; a resumed run submits
        survivors at the first un-journaled link and failed members at
        their failure link — different cohorts, each re-entering the chain
        mid-way). A cohort's links must be a contiguous range — the
        superstage hand-off and the Emgr's whole-chain drain guarantee it —
        and each cohort is micro-batched like a fused group, except that
        there is never a scalar remainder (the carrier is what orders link
        k before link k+1; see :func:`repro.fusion.plans.plan_chain`).
        Single-link cohorts fall back to plain per-stage fused groups.
        """
        cohorts: Dict[tuple, List[int]] = {}
        for m in sorted(per_member):
            links = tuple(sorted(per_member[m]))
            contiguous = links == tuple(range(links[0], links[0] + len(links)))
            cohorts.setdefault(links if contiguous else None, []).append(m)
        for links, member_idxs in cohorts.items():
            if links is None or len(links) < 2:
                # single link (or a defensive non-contiguous surprise):
                # per-stage fused groups, keyed by each task's own group tag
                regroup: Dict[str, List[Task]] = {}
                for m in member_idxs:
                    for task in per_member[m].values():
                        key = task.tags.get(GROUP_TAG) or "?"
                        regroup.setdefault(key, []).append(task)
                for members in regroup.values():
                    self._pack_group(members, out, free)
                continue
            entry = per_member[member_idxs[0]][links[0]]
            label = self._kernel_label_of(entry)
            compose = (len(links) >= self.fusion_min_chain
                       and self.breakers.allow(label, "chain"))
            mesh = self._plan_mesh(len(member_idxs), free, entry.slots,
                                   entry.tags) if compose else None
            if mesh is not None and not self.breakers.allow(label, "shard"):
                mesh = None
            if mesh is not None:
                sizes, mesh_shards, record = \
                    mesh.batches, mesh.n_shards, mesh.record()
            else:
                sizes = plan_chain(len(member_idxs), free, entry.slots,
                                   max_batch=self.fusion_max_batch)
                mesh_shards, record = 0, {"kind": "fused",
                                          "lanes": len(sizes), "scalar": 0}
            idx = 0
            for size in sizes:
                cohort = member_idxs[idx:idx + size]
                link_lists = [[per_member[m][k] for m in cohort]
                              for k in links]
                out.append(self._make_carrier(link_lists, compose=compose,
                                              mesh_shards=mesh_shards,
                                              plan=record))
                idx += size

    def _assemble_dag(self, per_node: Dict[int, Dict[int, Task]],
                      out: List[Task], free: Optional[int] = None) -> None:
        """Build ONE carrier from whatever nodes of a DAG round arrived.

        Unlike chains, a DAG is never split into lanes or scattered into
        per-stage groups: its reduction node consumes every member future,
        so any concurrent split would race the reduce against its own
        inputs. Every arrival — complete round or resume fragment — becomes
        a single carrier. The carrier *composes* (one device program over
        ``ensemble → reduce → broadcast → ensemble``) only when the round
        is complete (all ``n`` nodes present at their tagged width) and
        within the batch bound; otherwise it runs its nodes sequentially
        in-carrier, which preserves ordering and per-member semantics for
        fragments re-entering mid-round.
        """
        node_ids = sorted(per_node)
        links: List[List[Task]] = []
        for k in node_ids:
            links.append([per_node[k][m] for m in sorted(per_node[k])])
        first = links[0][0]
        tag = parse_dag_tag(first.tags) or {}
        n_total = int(tag.get("n") or len(node_ids))
        complete = node_ids == list(range(n_total))
        e_widths = set()
        if complete:
            for k, node in zip(node_ids, links):
                t = parse_dag_tag(node[0].tags) or {}
                want = int(t.get("w") or 1)
                if len(node) != want:
                    complete = False
                    break
                if t.get("r") != "r":
                    e_widths.add(want)
        width = max(len(node) for node in links)
        plan = plan_dag(n_total, width, dag=self.dag,
                        max_batch=self.fusion_max_batch)
        label = self._kernel_label_of(first)
        composed = plan.composed and complete
        if composed and not self.breakers.allow(label, "dag"):
            composed = False   # breaker open: sequential in-carrier nodes
        mesh = None
        if composed and len(e_widths) == 1:
            # custom combine fns (no "rk" tag) can't cross the mesh — the
            # batched combine sees only its shard's members
            if all((parse_dag_tag(node[0].tags) or {}).get("rk")
                   for node in links
                   if (parse_dag_tag(node[0].tags) or {}).get("r") == "r"):
                mesh = self._plan_mesh(width, free, first.slots, first.tags)
        if mesh is not None and not self.breakers.allow(label, "shard"):
            mesh = None
        if mesh is not None:
            plan = plan_dag(n_total, width, dag=self.dag,
                            max_batch=self.fusion_max_batch,
                            n_shards=mesh.n_shards)
        out.append(self._make_carrier(
            links, compose=composed,
            mesh_shards=mesh.n_shards if mesh is not None else 0,
            plan=plan.record(), dag=True))

    @staticmethod
    def _kernel_spec(task: Task) -> Optional[FusionSpec]:
        """The member's FusionSpec, looking through the API trampoline."""
        try:
            if task.executable == fusion_engine.TRAMPOLINE:
                fn = resolve_executable(task.kwargs["__fn__"])
            else:
                fn = task.resolve()
        except Exception:  # noqa: BLE001 - unresolvable: run it scalar
            return None
        return fusion_spec(fn)

    def _kernel_label_of(self, task: Task) -> Optional[str]:
        """The member's telemetry kernel label (the breaker-board key),
        looking through the API trampoline; cached per payload."""
        if task.executable == fusion_engine.TRAMPOLINE:
            key = task.kwargs.get("__fn__")
        else:
            key = task._fn if task._fn is not None else task.executable
        try:
            return self._label_cache[key]
        except (KeyError, TypeError):
            pass
        try:
            if task.executable == fusion_engine.TRAMPOLINE:
                fn = resolve_executable(task.kwargs["__fn__"])
            else:
                fn = task.resolve()
            label = fusion_engine._kernel_label(fn)
        except Exception:  # noqa: BLE001 - no callable: no breaker key
            label = None
        try:
            self._label_cache[key] = label
        except TypeError:
            pass
        return label

    def _make_carrier(self, links: List[List[Task]],
                      compose: bool = True, mesh_shards: int = 0,
                      plan: Optional[Dict[str, Any]] = None,
                      dag: bool = False) -> Task:
        # tenant accounting: the planners REUSE one plan record dict across
        # a group's carriers, so copy before stamping this carrier's tenant
        # mix onto it (the stamp differs per carrier)
        tenants = {m.tags.get("_tenant") or m.tags.get("_wf_ns")
                   for link in links for m in link}
        tenants.discard(None)
        if plan is not None:
            plan = dict(plan)
            plan["tenants"] = max(1, len(tenants))
        batch = _FusedBatch(links, compose=compose, mesh_shards=mesh_shards,
                            plan=plan, dag=dag)
        hints = [m.duration_hint for m in batch.members
                 if m.duration_hint is not None]
        n = len(links)
        width = (max(len(node) for node in links) if dag else len(links[0]))
        if dag:
            name = f"dag[{n}x{width}]:{links[0][0].name}"
            if mesh_shards:
                name = f"dag-shard[{mesh_shards}x{n}x{width}]:" \
                       f"{links[0][0].name}"
        elif mesh_shards:
            name = f"shard[{mesh_shards}x{n}x{width}]:{links[0][0].name}"
        else:
            name = (f"fused[{width}]:{links[0][0].name}" if n == 1
                    else f"chain[{n}x{width}]:{links[0][0].name}")
        carrier = Task(
            name=name, executable=f"fused://{n}x{width}",
            # a sharded carrier leases the WHOLE mesh all-or-nothing: one
            # member-width of slots per mesh device
            slots=links[0][0].slots * max(1, mesh_shards),
            duration_hint=max(hints) if hints else None)
        with self._fusion_lock:
            self._fused[carrier.uid] = batch
            for m in batch.members:
                self._member_carrier[m.uid] = carrier.uid
        # counters are individually locked: no need to hold _fusion_lock
        if len(tenants) > 1:
            self._fusion_count("cross_tenant_carriers")
        for label in tenants:
            self._tenant_count(label, "members", sum(
                1 for m in batch.members
                if (m.tags.get("_tenant") or m.tags.get("_wf_ns")) == label))
            if len(tenants) > 1:
                self._tenant_count(label, "shared_dispatches")
        if dag:
            self._fusion_count("dag_carriers")
        elif n > 1:
            self._fusion_count("chain_carriers")
        if mesh_shards:
            self._fusion_count("shard_carriers")
        return carrier

    # -- cancellation / introspection over carriers ---------------------------#

    def cancel(self, uids: List[str]) -> None:
        """Translate member uids to their carriers: a canceled member is
        skipped at fan-out time; a carrier whose every member is canceled
        is canceled itself (dequeued, or its dispatch interrupted). Members
        still parked in the shard hold buffer are simply dropped."""
        wanted = set(uids)
        with self._hold_lock:
            for k in list(self._held):
                kept = [t for t in self._held[k] if t.uid not in wanted]
                if not kept:
                    self._held.pop(k)
                    self._hold_seen.pop(k, None)
                    timer = self._hold_timers.pop(k, None)
                    if timer is not None:
                        timer.cancel()
                elif len(kept) != len(self._held[k]):
                    self._held[k] = kept
            for u in wanted:
                self._hold_arrived.pop(u, None)
        translated: List[str] = []
        emptied: List[str] = []
        with self._fusion_lock:
            for u in uids:
                carrier_uid = self._member_carrier.get(u)
                if carrier_uid is None:
                    translated.append(u)
                    continue
                self._fused_canceled.add(u)
                batch = self._fused.get(carrier_uid)
                if batch is not None:
                    batch.pending.discard(u)
                    if not batch.pending:
                        translated.append(carrier_uid)
                        emptied.append(carrier_uid)
        super().cancel(translated)
        if emptied:
            # a fully-canceled carrier dropped from the queue never runs:
            # reclaim its bookkeeping now rather than at stop()
            with self._lock:
                live = set(self._running) | {t.uid for t in self._queue}
            with self._fusion_lock:
                for carrier_uid in emptied:
                    if carrier_uid in live:
                        continue
                    batch = self._fused.pop(carrier_uid, None)
                    if batch is not None:
                        for m in batch.members:
                            self._member_carrier.pop(m.uid, None)
                            self._fused_canceled.discard(m.uid)

    def in_flight(self) -> List[str]:
        """Member uids, never carrier uids: EnTK's custody, failover and
        resubmission logic reasons about the tasks it submitted. Members
        parked in the shard hold buffer are in flight too — they have been
        accepted and will run at the latest when the hold deadline fires."""
        base = super().in_flight()
        with self._fusion_lock:
            out: List[str] = []
            for uid in base:
                batch = self._fused.get(uid)
                if batch is None:
                    out.append(uid)
                else:
                    out.extend(batch.pending)
        with self._hold_lock:
            for ms in self._held.values():
                out.extend(t.uid for t in ms)
        return out

    def running_since(self) -> Dict[str, float]:
        """Member uids with their carrier's elapsed time: the ExecManager's
        straggler watchdog reasons about the tasks it submitted, so a hung
        fused dispatch must surface as its (still-pending) members — each
        can then be speculatively cloned, and a clone is a lone scalar
        task whose win cancels the member inside the stuck batch."""
        base = super().running_since()
        with self._fusion_lock:
            out: Dict[str, float] = {}
            for uid, elapsed in base.items():
                batch = self._fused.get(uid)
                if batch is None:
                    out[uid] = elapsed
                else:
                    for member_uid in batch.pending:
                        out[member_uid] = elapsed
            return out

    # -- leasing --------------------------------------------------------------#

    def _can_start(self, task: Task) -> bool:
        with self._pool_lock:
            return len(self._pool) >= task.slots

    def _lease(self, task: Task) -> List[Any]:
        with self._pool_lock:
            if len(self._pool) < task.slots:
                # short lease: undo nothing, requeue the task — a partial
                # device set would silently break the task's mesh. For a
                # fused carrier this is the whole group's single requeue:
                # members are never requeued individually.
                self.lease_requeues += 1
                raise RequeueTask(
                    f"{task.uid} needs {task.slots} device slots, "
                    f"{len(self._pool)} in pool")
            ids = [self._pool.pop() for _ in range(task.slots)]
            self._leases[task.uid] = ids
        return [self._devices[i % len(self._devices)] for i in ids]

    def _unlease(self, task: Task) -> None:
        with self._pool_lock:
            self._pool.extend(self._leases.pop(task.uid, []))

    # -- execution ------------------------------------------------------------#

    def _run_task(self, task: Task, cancel_event: threading.Event) -> None:
        with self._fusion_lock:
            batch = self._fused.get(task.uid)
        if batch is None:
            return super()._run_task(task, cancel_event)
        self._run_fused(task, batch, cancel_event)

    def _run_fused(self, carrier: Task, batch: _FusedBatch,
                   cancel_event: threading.Event) -> None:
        """Carrier worker: lease devices all-or-nothing, resolve + stack +
        enqueue the batched dispatches, then hand the carrier to the
        completion drainer and RETURN — the worker never parks in
        ``block_until_ready``. The drainer owns fan-out, unlease and
        release, so the lease's lifetime spans the whole chain while the
        scheduler is already stacking the next carrier. No carrier-level
        fault injection or staging — those are member semantics, and the
        engine applies the injector per member."""
        try:
            devices = self._lease(carrier)
        except RequeueTask:
            self._release(carrier)
            if not self._stop.is_set():
                self._requeue(carrier)   # whole group, once, at the front
            return

        tenant_of = {m.uid: (m.tags.get("_tenant") or m.tags.get("_wf_ns"))
                     for m in batch.members}
        # one shared counter handle per distinct tenant: deliver() runs per
        # member completion, so resolve the registry lookup once up front
        completions_of = {
            label: self.metrics.counter(TENANT_EVENTS, tenant=label,
                                        field="completions")
            for label in set(tenant_of.values()) if label is not None}

        def deliver(c: TaskCompletion) -> None:
            if batch.plan is not None:
                # postmortem perf debugging: every member's journal record
                # carries the carrier's chosen plan (mesh shape or lanes)
                c.plan = batch.plan
            label = tenant_of.get(c.uid)
            with self._fusion_lock:
                batch.pending.discard(c.uid)
            counter = completions_of.get(label)
            if counter is not None:
                counter.inc()
            self._deliver(c)

        mesh_devices = None
        if batch.mesh_shards:
            # an oversubscribed pool can lease the same physical device
            # twice; a mesh needs distinct devices — when the lease
            # collapses short, run the carrier on the single-device path
            uniq = list(dict.fromkeys(devices))
            if len(uniq) >= batch.mesh_shards:
                mesh_devices = uniq[:batch.mesh_shards]
        exe = fusion_engine.CarrierExecution(
            batch.links, devices, cancel_event, deliver,
            canceled=self._fused_canceled,
            fault_injector=self.fault_injector, compose=batch.compose,
            mesh_devices=mesh_devices)
        # exe.tier reflects what will ACTUALLY run ("shard" only when the
        # lease produced a real mesh), unlike the plan's mesh_shards hint
        self.metrics.counter(CARRIERS_TOTAL, tier=exe.tier).inc()
        # registered BEFORE the dispatches run so the drainer can fan out
        # early links of a chain while a later link is still dispatching
        # (mid-chain journal records exist the moment a link resolves)
        self._drain_q.put((carrier, batch, exe))
        span = tel.NOOP_SPAN
        if tel.enabled():
            span = tel.span(
                "carrier.dispatch", "rts", carrier=carrier.name,
                tier=exe.tier, links=len(batch.links),
                width=max(len(link) for link in batch.links),
                members=len(batch.members), mesh_shards=batch.mesh_shards,
                tenants=",".join(sorted(
                    str(t) for t in set(tenant_of.values())
                    if t is not None)))
        with span:
            exe.dispatch()

    def _record_breaker(self, batch: _FusedBatch, exe: Any, ok: bool) -> None:
        """Feed one carrier outcome to the breaker board under the tier it
        actually ran ("dag-shard" records as "dag" — the composition is
        what the consult gated). Never raises: breaker accounting must not
        disturb the drainer's unconditional lease release."""
        try:
            tier = getattr(exe, "tier", None)
            if tier is None or not batch.members:
                return
            tier = {"dag-shard": "dag"}.get(tier, tier)
            self.breakers.record(
                self._kernel_label_of(batch.members[0]), tier, ok)
        except Exception:  # noqa: BLE001 - accounting only
            pass

    def _drain_loop(self) -> None:
        """One drainer of the pool: resolve a dispatched carrier's outputs,
        fan out its completions (link order holds within the carrier;
        carriers on different drainers complete independently), then (and
        only then) return its devices — a canceled or crashed carrier can
        never leak its lease, because this release is unconditional."""
        while True:
            item = self._drain_q.get()
            if item is None:
                return
            carrier, batch, exe = item
            try:
                with tel.span("carrier.drain", "rts",
                              carrier=carrier.name,
                              tier=getattr(exe, "tier", "?"),
                              members=len(batch.members)):
                    stats = exe.drain(stop_event=self._stop)
                # each per-kind increment is its own locked counter — the
                # drainer pool can merge concurrently without a lost update
                # (the fusion_stats accumulation race this PR fixes)
                for k, v in stats.items():
                    if v:
                        self._fusion_count(k, v)
                # breaker board: a carrier that degraded (or fell back to
                # scalar) is a failure OF ITS TIER — member task failures
                # ("failed") are not, the tier executed them correctly
                self._record_breaker(
                    batch, exe,
                    ok=not (stats.get("degraded")
                            or stats.get("scalar_fallback")))
            except Exception:  # noqa: BLE001 - engine failed outside guards
                self._record_breaker(batch, exe, ok=False)
                exc = traceback.format_exc(limit=10)
                now = time.time()
                with self._fusion_lock:
                    undelivered = [m for m in batch.members
                                   if m.uid in batch.pending
                                   and m.uid not in self._fused_canceled]
                for m in undelivered:
                    with self._fusion_lock:
                        batch.pending.discard(m.uid)
                    self._deliver(TaskCompletion(
                        uid=m.uid, exit_code=1, exception=exc,
                        started_at=now, completed_at=now))
            finally:
                self._unlease(carrier)
                self._release(carrier)
                with self._fusion_lock:
                    self._fused.pop(carrier.uid, None)
                    for m in batch.members:
                        self._member_carrier.pop(m.uid, None)
                        self._fused_canceled.discard(m.uid)

    def _execute(self, task: Task, cancel_event: threading.Event,
                 stall: float):
        devices = self._lease(task)
        try:
            fn = None
            try:
                fn = task.resolve()
            except Exception:  # noqa: BLE001 - sleep:// tasks have no callable
                pass
            if fn is None:
                return super()._execute(task, cancel_event, stall)
            try:
                sig = inspect.signature(fn)
                if "devices" in sig.parameters:
                    task.kwargs = dict(task.kwargs)
                    task.kwargs["devices"] = devices
            except (TypeError, ValueError):
                pass
            kernel = fn
            if task.executable == fusion_engine.TRAMPOLINE:
                try:  # label the USER kernel, not the api trampoline
                    kernel = resolve_executable(task.kwargs["__fn__"])
                except Exception:  # noqa: BLE001 - label only, never fatal
                    pass
            t0 = time.perf_counter()
            result = super()._execute(task, cancel_event, stall)
            tel.observe_dispatch(
                getattr(kernel, "__name__", None) or str(kernel),
                "scalar", time.perf_counter() - t0)
            return result
        finally:
            self._unlease(task)
