"""Fused execution engine: N congruent tasks → batched JAX dispatches.

Given a micro-batch of member tasks (same kernel, congruent kwargs — see
:mod:`repro.fusion.groups`), the engine

1. resolves each member's callable and kwargs (trampoline-aware: tasks
   compiled by ``repro.api`` carry ``{"__future__": ...}`` placeholders
   that resolve against the result store, exactly as the scalar path does),
2. stacks the batch kwargs onto a leading axis — padding declared
   variable-length arguments to the group maximum by edge replication,
   which is safe for per-row kernels because padded rows are trimmed from
   the outputs before delivery,
3. dispatches **once per link**: the kernel's hand-written batched
   implementation when it has one, else ``jax.vmap`` of the scalar kernel,
   jitted with a cache keyed on (kernel, static arguments) so repeated
   micro-batches of one ensemble reuse the trace,
4. fans the stacked output back out as one completion per member — every
   member gets its own DONE/FAILED event, so journal records, retry
   budgets and resume semantics are per-task, exactly as if the members
   had run scalar.

Chain fusion (PR 5) extends this across stages: :class:`ChainExecution`
takes a *list* of links (one micro-batch of members through L elementwise
stages), composes consecutive ``vmap``-able links into a single jitted
program (``jit(vmap(g∘f))``) and carries the stacked intermediate outputs
between links device-resident — the host never re-stacks, and the control
plane never sits between stages. Execution is **asynchronous**: the
carrier's worker thread only resolves inputs, stacks and enqueues the
dispatches (:meth:`ChainExecution.dispatch`), streaming per-link records to
a completion drainer which blocks on the device output, fans out the
per-stage per-member completions in link order (:meth:`ChainExecution.drain`)
and releases the device lease. Host-side stacking of micro-batch *n+1*
therefore overlaps device compute of micro-batch *n*.

SPMD sharding (PR 6) widens one carrier across the whole device mesh: when
the RTS leases several distinct devices for a carrier (``mesh_devices``),
the stacked member kwargs are placed with ``NamedSharding`` over a 1-D
``Mesh`` on the member axis and the composed program (or hand-batched
kernel) executes under ``shard_map`` — ONE XLA program spans every leased
device, chain intermediates stay sharded end-to-end between links, and the
fan-out hands members sharding-aware lazy slices (a per-member read touches
one device's shard, never a batch gather). Every sharded wrapper passes
``check_vma=False``: user kernels may contain ``pallas_call``, which has no
replication rule. Any sharded-dispatch failure degrades through the
existing ladder (per-stage fused on one device, then per-member scalar).

Failure isolation: a member whose outputs contain non-finite values at
link *k* FAILS at *k* and its downstream links fail with an upstream
marker, while every other member completes; an exception raised by a
chain/batched dispatch degrades the remaining links to per-stage fused
execution (consuming the already-resolved upstream values), and a failing
per-stage dispatch degrades further to per-member scalar execution so only
the actually-culpable members fail. Resume of a partially-failed batch
therefore re-runs exactly the failed members, re-entering mid-chain from
the last journaled link.
"""

from __future__ import annotations

import math
import threading
import time
import traceback
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry as tel
from ..core.pst import Task, resolve_executable
from ..rts.base import TaskCompletion
from .groups import FusionSpec, fusion_spec, parse_dag_tag, reduction_spec
from .handles import ArrayResult, LazySlice

Deliver = Callable[[TaskCompletion], None]

# jit-cache accounting: hit / miss (a miss IS a trace+compile — what the
# docs call a recompile) / uncached (a non-hashable statics key bypasses
# the cache entirely, retracing every dispatch) / eviction (LRU pressure:
# the next same-key dispatch will recompile).
_JIT_HITS = tel.counter("fusion_jit_cache_total", outcome="hit")
_JIT_MISSES = tel.counter("fusion_jit_cache_total", outcome="miss")
_JIT_UNCACHED = tel.counter("fusion_jit_cache_total", outcome="uncached")
_JIT_EVICTIONS = tel.counter("fusion_jit_cache_evictions_total")


def _kernel_label(fn: Any) -> str:
    """Stable per-kernel metric label (the dispatch-latency family key)."""
    return getattr(fn, "__name__", None) or str(fn)

#: chaos-plane hook (``repro.chaos``): when set, every carrier consults it
#: once at dispatch time with the execution object; True ⇒ the composed
#: dispatch raises and the carrier walks the degrade ladder (per-stage
#: fused → per-member scalar). Members are never lost — the hook exercises
#: the same path a real mid-dispatch device failure takes.
CARRIER_FAULT: Optional[Callable[[Any], bool]] = None

TRAMPOLINE = "reg://_api.call"

# (kernel, static kwargs) -> jitted vmapped callable; bounds retracing to
# one per (ensemble kernel × static configuration), not one per micro-batch.
# LRU-bounded: a workflow sweeping a static argument (e.g. a line search
# over a static dv) would otherwise leak one trace per value for the
# process lifetime — long-lived multi-workflow processes are a target.
_JIT_CACHE_MAX = 64
_jit_cache: "OrderedDict[Tuple, Callable[..., Any]]" = OrderedDict()
_jit_lock = threading.Lock()

# task uid -> (fn, args, kwargs) with ArrayResult handles unwrapped: the
# resolve + unwrap recursion over a member's kwargs showed up hot in the
# 10k-member stacking path (it ran once at Emgr pack time for the kernel
# spec and again per dispatch). Entries are dropped when the member's
# completion is delivered, so retries always re-resolve.
_CALL_CACHE_MAX = 16384
_call_cache: "OrderedDict[str, Tuple[Callable, list, dict]]" = OrderedDict()
_call_lock = threading.Lock()

# (kernel, frozenset(kwarg names)) -> (static names, shared names, batch
# names): the kwarg partition is identical for every micro-batch of a group.
_part_cache: "OrderedDict[Tuple, Tuple[tuple, tuple, tuple]]" = OrderedDict()
_PART_CACHE_MAX = 256

# (kernel, statics key) -> output treedef, reused across micro-batches of
# the same group (flatten_up_to skips re-deriving the structure).
_treedef_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
_TREEDEF_CACHE_MAX = 256


class Incongruent(Exception):
    """Members cannot share a dispatch; the caller runs them scalar."""


# --------------------------------------------------------------------------- #
# Member resolution
# --------------------------------------------------------------------------- #

def _unwrap(value: Any) -> Any:
    """Unwrap ArrayResult handles nested in resolved kwargs."""
    if isinstance(value, ArrayResult):
        return value.value
    if isinstance(value, (list, tuple)):
        return type(value)(_unwrap(v) for v in value)
    if isinstance(value, dict):
        return {k: _unwrap(v) for k, v in value.items()}
    return value


def _resolve_call(task: Task, overrides: Optional[Dict[str, Any]]
                  ) -> Tuple[Callable[..., Any], list, dict]:
    if task.executable == TRAMPOLINE:
        from ..api.runtime import resolve as resolve_placeholders
        ns = task.kwargs["__ns__"]
        fn = resolve_executable(task.kwargs["__fn__"])
        if overrides:
            args = _resolve_over(task.kwargs["__args__"], ns, overrides)
            kwargs = _resolve_over(task.kwargs["__kwargs__"], ns, overrides)
        else:
            args = resolve_placeholders(task.kwargs["__args__"], ns)
            kwargs = resolve_placeholders(task.kwargs["__kwargs__"], ns)
        return fn, [_unwrap(a) for a in args], \
            {k: _unwrap(v) for k, v in kwargs.items()}
    return task.resolve(), [_unwrap(a) for a in task.args], \
        {k: _unwrap(v) for k, v in task.kwargs.items()}


def _resolve_over(value: Any, ns: str, overrides: Dict[str, Any]) -> Any:
    """Placeholder resolution that prefers chain-carried values over the
    store (mid-chain degrades must never race the Dequeue's store routing;
    the carrier already holds the upstream member values)."""
    from ..api.runtime import FUTURE_KEY
    from ..core.results import STORE

    if isinstance(value, dict):
        if set(value) == {FUTURE_KEY}:
            name = value[FUTURE_KEY]
            if name in overrides:
                return overrides[name]
            return STORE.get(ns, name)
        return {k: _resolve_over(v, ns, overrides) for k, v in value.items()}
    if isinstance(value, list):
        return [_resolve_over(v, ns, overrides) for v in value]
    return value


def member_call(task: Task, overrides: Optional[Dict[str, Any]] = None
                ) -> Tuple[Callable[..., Any], list, dict]:
    """Resolve one member task to (fn, args, kwargs), placeholders resolved
    and ArrayResult handles unwrapped.

    Tasks compiled by the declarative API run through the registered
    trampoline; fusing must look *through* it to the user kernel, resolving
    the same future placeholders the trampoline would. The resolution is
    cached per task (the Emgr's kernel-spec probe and the dispatch both
    need it); callers must treat the returned structures as read-only.
    """
    if overrides:
        return _resolve_call(task, overrides)
    with _call_lock:
        hit = _call_cache.get(task.uid)
        if hit is not None:
            _call_cache.move_to_end(task.uid)
            return hit
    call = _resolve_call(task, None)
    with _call_lock:
        _call_cache[task.uid] = call
        while len(_call_cache) > _CALL_CACHE_MAX:
            _call_cache.popitem(last=False)
    return call


def drop_member_call(uid: str) -> None:
    """Invalidate a member's cached resolution (delivery/retry boundary)."""
    with _call_lock:
        _call_cache.pop(uid, None)


# --------------------------------------------------------------------------- #
# Batch preparation
# --------------------------------------------------------------------------- #

def _partition(fn: Callable, spec: FusionSpec, kwargs0: dict
               ) -> Tuple[tuple, tuple, tuple]:
    """(static names, shared names, batch names) for one group's kwargs —
    cached: the partition never changes between micro-batches of a group."""
    key = (fn, frozenset(kwargs0))
    with _jit_lock:
        part = _part_cache.get(key)
        if part is not None:
            _part_cache.move_to_end(key)
            return part
    statics = tuple(k for k in spec.static_argnames if k in kwargs0)
    shareds = tuple(k for k in spec.shared_argnames if k in kwargs0)
    batch = tuple(k for k in kwargs0
                  if k not in statics and k not in shareds)
    part = (statics, shareds, batch)
    with _jit_lock:
        _part_cache[key] = part
        while len(_part_cache) > _PART_CACHE_MAX:
            _part_cache.popitem(last=False)
    return part


def _placed_nbytes(value: Any, target: Any) -> int:
    """Bytes of ``value``'s array leaves that placing them on ``target`` (a
    sharding, or None for JAX's default device) moves: host arrays, and
    device arrays laid out otherwise (a copy on each of the target's
    devices, in any order, is in place). A leaf counts once, however many
    devices it is replicated to."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        if not isinstance(leaf, jax.Array):
            total += np.asarray(leaf).nbytes
            continue
        have = leaf.sharding
        if target is None or have.is_equivalent_to(target, leaf.ndim) or (
                have.is_fully_replicated and target.is_fully_replicated
                and have.device_set == target.device_set):
            continue
        total += leaf.nbytes
    return total


def _prepare(calls: Sequence[Tuple[Callable, list, dict]],
             pad_to: Optional[int] = None, device: Any = None,
             placed: Optional[List[int]] = None):
    """Validate congruence and stack the batch kwargs.

    Returns ``(fn, spec, static_kw, shared_kw, stacked, valid_lens, padded_b)``
    where ``stacked`` maps batch kwarg → array with leading axis
    ``padded_b`` (the batch axis bucketed to a power of two, or to
    ``pad_to`` when a chain entry already fixed the bucket) and
    ``valid_lens`` is the per-member unpadded length (None when no padding
    was needed). ``shared_kw`` includes the kernel's ``operands``. With
    ``device`` (a single-device carrier's lease) every stacked and shared
    array is placed there, so the dispatch runs on the leased device.
    ``placed`` (tracing only) gets the bytes that placement moves, counted
    before it: the caller of a mesh carrier counts its own placement.
    """
    import jax
    import jax.numpy as jnp

    target = None
    if placed is not None and device is not None:
        target = jax.sharding.SingleDeviceSharding(device)

    fn0, args0, kwargs0 = calls[0]
    spec = fusion_spec(fn0)
    if spec is None:
        raise Incongruent("kernel lost its fusion marker")
    keys0 = set(kwargs0)
    for fn, args, kwargs in calls:
        if fn is not fn0 or args or set(kwargs) != keys0:
            raise Incongruent("members disagree on kernel or kwarg names")
    static_names, shared_names, batch_keys = _partition(fn0, spec, kwargs0)
    static_kw = {k: kwargs0[k] for k in static_names}
    for _, _, kwargs in calls[1:]:
        for k, v in static_kw.items():
            if kwargs[k] != v:
                raise Incongruent(f"static argument {k!r} differs "
                                  f"within the group")
    shared_kw = {k: kwargs0[k] for k in shared_names}
    for _, _, kwargs in calls[1:]:
        for k, v0 in shared_kw.items():
            v = kwargs[k]
            if v is v0:
                continue  # the common case: one object shared by reference
            a0, a1 = np.asarray(v0), np.asarray(v)
            if (a0.shape != a1.shape or a0.dtype != a1.dtype
                    or not np.array_equal(a0, a1)):
                # the group key cannot see shared VALUES (arrays are not
                # hashable into it), so two congruent-looking ensembles
                # with different shared arrays must be caught here — a
                # silent first-member pick would compute every other
                # member against the wrong array
                raise Incongruent(
                    f"shared argument {k!r} differs within the group")

    stacked: Dict[str, Any] = {}
    valid_lens: Optional[List[int]] = None
    for k in batch_keys:
        raw = [kwargs[k] for _, _, kwargs in calls]
        # stack host-side unless a leaf is already device-resident (an
        # ArrayResult from an upstream fused stage): per-member
        # jnp.asarray + device jnp.stack costs one dispatch per member —
        # exactly the per-task overhead fusion exists to remove
        xp = jnp if any(isinstance(v, jax.Array) for v in raw) else np
        leaves = [xp.asarray(v) for v in raw]
        if xp is jnp and device is not None:
            # upstream members may have run on other devices' carriers
            if placed is not None:
                placed.append(_placed_nbytes(leaves, target))
            leaves = jax.device_put(leaves, device)
        shapes = {leaf.shape for leaf in leaves}
        if len(shapes) > 1:
            if k not in spec.pad_argnames:
                raise Incongruent(
                    f"argument {k!r} varies in shape but is not declared "
                    f"in pad_argnames")
            if any(leaf.ndim == 0 or leaf.shape[1:] != leaves[0].shape[1:]
                   for leaf in leaves):
                raise Incongruent(
                    f"pad argument {k!r} members differ beyond axis 0")
            lens = [int(leaf.shape[0]) for leaf in leaves]
            if any(n == 0 for n in lens):
                raise Incongruent(f"pad argument {k!r} has an empty member")
            target = max(lens)
            leaves = [
                leaf if n == target else xp.concatenate(
                    [leaf, xp.repeat(leaf[-1:], target - n, axis=0)])
                for leaf, n in zip(leaves, lens)]
            if valid_lens is None:
                valid_lens = lens
            elif valid_lens != lens:
                raise Incongruent("pad arguments disagree on member lengths")
        stacked[k] = xp.stack(leaves)
    # Bucket the batch axis to the next power of two by duplicating the
    # last member: jit compiles once per (kernel, statics, SHAPE), and an
    # Emgr submitting adaptively-sized micro-batches would otherwise pay a
    # fresh XLA compile (~100x a dispatch) for nearly every carrier. The
    # duplicate rows compute and are discarded at fan-out. A chain entry
    # fixes the bucket for every downstream link (``pad_to``): the carried
    # axis must stay congruent through the whole composed program.
    b = len(calls)
    target_b = pad_to if pad_to is not None \
        else 1 << max(0, b - 1).bit_length()
    if target_b < b:
        raise Incongruent("chain links disagree on member count")
    if target_b != b:
        for k, arr in stacked.items():
            xp = jnp if not isinstance(arr, np.ndarray) else np
            stacked[k] = xp.concatenate(
                [arr, xp.repeat(arr[-1:], target_b - b, axis=0)])
    if spec.operands is not None:
        shared_kw.update(spec.operands(**static_kw))
    if placed is not None:
        placed.append(_placed_nbytes((stacked, shared_kw), target))
    if device is not None:
        stacked = jax.device_put(stacked, device)
        shared_kw = {k: jax.device_put(v, device)
                     if isinstance(v, jax.Array) else v
                     for k, v in shared_kw.items()}
    return fn0, spec, static_kw, shared_kw, stacked, valid_lens, target_b


def _continuation_calls(tasks: Sequence[Task], prev_tasks: Sequence[Task]
                        ) -> Tuple[List[Tuple[Callable, list, dict]], str]:
    """Resolve a continuation link's members WITHOUT touching their carried
    input: the carry arrives device-resident from the previous link, so its
    placeholder must not hit the result store (mid-chain it has no value
    there yet — that is the whole point). Returns the carry kwarg name."""
    from ..api.runtime import FUTURE_KEY
    from ..api.runtime import resolve as resolve_placeholders

    calls: List[Tuple[Callable, list, dict]] = []
    names: set = set()
    for t, prev in zip(tasks, prev_tasks):
        if t.executable != TRAMPOLINE:
            raise Incongruent("chain link is not a data-flow task")
        if t.kwargs.get("__args__"):
            raise Incongruent("chain link has positional args")
        ns = t.kwargs["__ns__"]
        fn = resolve_executable(t.kwargs["__fn__"])
        carry_k = None
        other: Dict[str, Any] = {}
        for k, v in (t.kwargs.get("__kwargs__") or {}).items():
            if isinstance(v, dict) and set(v) == {FUTURE_KEY}:
                if v[FUTURE_KEY] == prev.name and carry_k is None:
                    carry_k = k
                    continue
                raise Incongruent("chain link consumes a non-chain future")
            other[k] = _unwrap(resolve_placeholders(v, ns))
        if carry_k is None:
            raise Incongruent("chain link does not consume its predecessor")
        calls.append((fn, [], other))
        names.add(carry_k)
    if len(names) != 1:
        raise Incongruent("chain links disagree on the carry kwarg")
    return calls, names.pop()


# --------------------------------------------------------------------------- #
# Dispatch
# --------------------------------------------------------------------------- #

def _statics_key(static_kw: dict) -> Optional[Tuple]:
    try:
        key = tuple(sorted(static_kw.items()))
        hash(key)
        return key
    except TypeError:
        return None


def _jit_cached(cache_key: Optional[Tuple], build: Callable[[], Callable]
                ) -> Callable:
    if cache_key is None:
        _JIT_UNCACHED.inc()
        return build()
    with _jit_lock:
        jitted = _jit_cache.get(cache_key)
        if jitted is not None:
            _jit_cache.move_to_end(cache_key)
            _JIT_HITS.inc()
            return jitted
    _JIT_MISSES.inc()
    jitted = build()
    with _jit_lock:
        _jit_cache[cache_key] = jitted
        while len(_jit_cache) > _JIT_CACHE_MAX:
            _jit_cache.popitem(last=False)
            _JIT_EVICTIONS.inc()
    return jitted


def _dispatch(fn, spec: FusionSpec, static_kw: dict, shared_kw: dict,
              stacked: dict):
    """One batched device dispatch over the stacked kwargs."""
    import jax

    if spec.batched is not None:
        return spec.batched(**stacked, **static_kw, **shared_kw)
    skey = _statics_key(static_kw)
    cache_key = None if skey is None else (fn, skey, tuple(sorted(stacked)))

    def build():
        def call(batched: dict, shared: dict):
            return fn(**batched, **shared, **static_kw)
        return jax.jit(jax.vmap(call, in_axes=(0, None)))

    return _jit_cached(cache_key, build)(stacked, shared_kw)


class _LinkPlan:
    """One prepared chain link: resolved kernel + stacked batch kwargs."""

    __slots__ = ("tasks", "fn", "spec", "static_kw", "shared_kw", "stacked",
                 "valid_lens", "carry_name", "statics_key", "t_dispatch")

    def __init__(self, tasks, fn, spec, static_kw, shared_kw, stacked,
                 valid_lens, carry_name) -> None:
        self.tasks = tasks
        self.fn = fn
        self.spec = spec
        self.static_kw = static_kw
        self.shared_kw = shared_kw
        self.stacked = stacked
        self.valid_lens = valid_lens
        self.carry_name = carry_name
        self.statics_key = _statics_key(static_kw)
        self.t_dispatch: Optional[float] = None


def _mesh_key(mesh) -> Tuple:
    """Hashable identity of a mesh (device ids) for the jit cache."""
    return tuple(int(d.id) for d in mesh.devices.flat)


def build_mesh(devices: Optional[Sequence[Any]]):
    """A 1-D member-axis ``Mesh`` over ``devices``, or None when the lease
    is not meshable (empty, placeholder device names, duplicate physical
    devices from logical-slot oversubscription)."""
    if not devices:
        return None
    try:
        import jax
        from jax.sharding import Mesh

        uniq = list(dict.fromkeys(devices))
        if len(uniq) != len(devices):
            return None
        if any(not isinstance(d, jax.Device) for d in uniq):
            return None
        return Mesh(np.array(uniq, dtype=object), ("m",))
    except Exception:  # noqa: BLE001 - unmeshable lease ⇒ micro-batch path
        return None


def lease_device(devices: Optional[Sequence[Any]]):
    """The device a single-device carrier runs on: the first device of its
    lease, or None when the lease holds placeholder names (unit-test pools)
    and placement is left to JAX's default device."""
    if not devices:
        return None
    import jax

    return devices[0] if isinstance(devices[0], jax.Device) else None


def shard_pad(n_members: int, n_shards: int) -> int:
    """Padded batch axis for a sharded dispatch: ``n_shards`` equal shards,
    each bucketed to a power of two — the compile-shape bucketing rule of
    the micro-batch path, applied per shard. Past 512 members per shard
    the bucket quantum flattens to 256: pow2 bucketing there would pad a
    wide dispatch by up to ~2x in dead compute to save at most a handful
    of cached compiles."""
    per = max(1, math.ceil(n_members / max(1, n_shards)))
    if per > 512:
        return n_shards * (256 * math.ceil(per / 256))
    return n_shards * (1 << max(0, per - 1).bit_length())


def _composed_segment(plans: Sequence[_LinkPlan], mesh=None) -> Callable:
    """One jitted program running consecutive vmap-able links back to back —
    literally ``jit(vmap(g∘f))`` for a 2-link segment. The carried
    intermediate is an XLA value inside the program: it never materializes
    on the host, and XLA is free to fuse across the link boundary. Every
    link's output is still returned (the fan-out owes each stage its
    per-member completions).

    With ``mesh``, the whole segment runs under ``shard_map`` on the member
    axis: one program spans every mesh device and the carried intermediates
    stay sharded across link boundaries."""
    import jax

    metas = [(p.fn, dict(p.static_kw), p.carry_name) for p in plans]

    def seg(stacked_list, shared_list, carry):
        outs = []
        for (fn, static_kw, carry_name), kwb, shb in zip(
                metas, stacked_list, shared_list):
            kw = dict(kwb)
            if carry_name is not None:
                kw[carry_name] = carry
            def call(kw_, sh_, fn=fn, static_kw=static_kw):
                return fn(**kw_, **sh_, **static_kw)
            out = jax.vmap(call, in_axes=(0, None))(kw, shb)
            outs.append(out)
            carry = out
        return outs

    cache_key: Optional[Tuple] = tuple(
        (p.fn, p.statics_key, tuple(sorted(p.stacked)), p.carry_name,
         tuple(sorted(p.shared_kw))) for p in plans)
    if any(p.statics_key is None for p in plans):
        cache_key = None

    if mesh is None:
        return _jit_cached(("chain", cache_key) if cache_key else None,
                           lambda: jax.jit(seg))

    from jax.sharding import PartitionSpec as P

    def build():
        # check_vma=False: links may contain pallas_call (no replication
        # rule); out_specs is a pytree prefix over every link's output
        return jax.jit(jax.shard_map(
            seg, mesh=mesh, in_specs=(P("m"), P(), P("m")),
            out_specs=P("m"), check_vma=False))

    return _jit_cached(
        ("chain-shard", _mesh_key(mesh), cache_key) if cache_key else None,
        build)


# --------------------------------------------------------------------------- #
# Fan-out
# --------------------------------------------------------------------------- #

class _FanOut:
    """Turns one stacked output pytree into per-member results.

    Built once per dispatch: per-member-scalar leaves (ndim == 1) transfer
    to the host in ONE copy and fan out as Python scalars; higher-rank
    leaves stay on device and members receive zero-copy LAZY slices
    (:class:`~repro.fusion.handles.LazySlice`) — the gather only runs if a
    consumer reads the handle, so chain-internal stages deliver at host
    bookkeeping cost. The finite mask is likewise one reduction per leaf, a
    single device→host sync for the whole batch instead of one per member.
    """

    def __init__(self, out: Any, n_live: int, check_finite: bool,
                 valid_lens: Optional[List[int]],
                 treedef_key: Optional[Tuple] = None) -> None:
        import jax
        import jax.numpy as jnp

        self.leaves, self.treedef = self._flatten(out, treedef_key)
        self.valid_lens = valid_lens
        self.padded_len = max(valid_lens) if valid_lens else None
        self.ok = np.ones(n_live, bool)
        self.host: Dict[int, np.ndarray] = {}
        for idx, leaf in enumerate(self.leaves):
            arr = jnp.asarray(leaf)
            self.leaves[idx] = arr
            if arr.ndim == 1:
                self.host[idx] = np.asarray(arr)
            if check_finite and jnp.issubdtype(arr.dtype, jnp.floating):
                fin = jnp.isfinite(arr.reshape(arr.shape[0], -1)).all(axis=1)
                self.ok &= np.asarray(fin)[:n_live]

    @staticmethod
    def _flatten(out: Any, treedef_key: Optional[Tuple]):
        import jax

        if treedef_key is not None and treedef_key[-1] is None:
            # unhashable statics: different static configs of one kernel
            # would collide on (fn, None) and flatten_up_to would silently
            # mis-structure the other config's output — same rule as the
            # jit cache, which also disables itself for unhashable statics
            treedef_key = None
        if treedef_key is not None:
            with _jit_lock:
                cached = _treedef_cache.get(treedef_key)
            if cached is not None:
                try:
                    return list(cached.flatten_up_to(out)), cached
                except (ValueError, TypeError):
                    pass  # structure changed: re-derive below
        leaves, treedef = jax.tree_util.tree_flatten(out)
        if treedef_key is not None:
            with _jit_lock:
                _treedef_cache[treedef_key] = treedef
                while len(_treedef_cache) > _TREEDEF_CACHE_MAX:
                    _treedef_cache.popitem(last=False)
        return leaves, treedef

    def member(self, i: int) -> Any:
        import jax

        def pick(idx: int) -> Any:
            if idx in self.host:
                return self.host[idx][i].item()
            leaf = self.leaves[idx]
            trim = None
            if (self.valid_lens is not None and leaf.ndim >= 2
                    and leaf.shape[1] == self.padded_len
                    and self.valid_lens[i] < self.padded_len):
                trim = self.valid_lens[i]
            return LazySlice(leaf, i, trim=trim)

        return jax.tree_util.tree_unflatten(
            self.treedef, [pick(idx) for idx in range(len(self.leaves))])


# --------------------------------------------------------------------------- #
# Single-link entry point (also the chain's per-stage degrade unit)
# --------------------------------------------------------------------------- #

def execute_fused(
    members: Sequence[Task],
    devices: Sequence[Any],
    cancel_event: threading.Event,
    deliver: Deliver,
    *,
    canceled: Optional[set] = None,
    fault_injector: Optional[Callable[[Task], bool]] = None,
    overrides: Optional[Dict[str, Any]] = None,
) -> Dict[str, int]:
    """Run ``members`` as one fused dispatch; deliver one completion each.

    Returns execution statistics (``fused`` / ``scalar_fallback`` /
    ``failed`` member counts). ``canceled`` uids are skipped without a
    completion (the same semantics as dropping a queued task on cancel);
    ``fault_injector`` is honoured per member so the failure experiments
    behave identically on the fused path. ``overrides`` (chain degrade)
    resolves future placeholders from the carrier's already-computed
    upstream values instead of the store.
    """
    import jax

    canceled = canceled or set()
    # "dispatches" counts BATCHED dispatches only: a micro-batch that
    # degraded to per-member scalar execution contributes zero, so the
    # benchmark's dispatch counts cannot mask a silently-degraded run
    stats = {"fused": 0, "scalar_fallback": 0, "failed": 0, "dispatches": 0}
    started = time.time()

    def finish(task: Task, exit_code: int, result: Any = None,
               exception: Optional[str] = None, n_live: int = 1) -> None:
        # invalidate BEFORE the cancel skip: a canceled member delivers no
        # completion, but leaving its resolved arrays pinned in the call
        # cache until LRU eviction would retain them long past the run
        drop_member_call(task.uid)
        if task.uid in canceled:
            return
        now = time.time()
        if exit_code == 1:
            stats["failed"] += 1
        deliver(TaskCompletion(
            uid=task.uid, exit_code=exit_code, result=result,
            exception=exception, started_at=started, completed_at=now,
            execution_seconds=(now - started) / max(1, n_live)))

    live: List[Task] = []
    for task in members:
        if task.uid in canceled:
            continue
        if cancel_event.is_set():
            finish(task, -2)
            continue
        if fault_injector is not None and fault_injector(task):
            finish(task, 1, exception="injected fault")
            continue
        live.append(task)
    if not live:
        return stats

    try:
        calls = [member_call(t, overrides) for t in live]
        fn, spec, static_kw, shared_kw, stacked, valid_lens, _ = \
            _prepare(calls, device=lease_device(devices))
        t0 = time.perf_counter()
        out = _dispatch(fn, spec, static_kw, shared_kw, stacked)
        out = jax.block_until_ready(out)
        tel.observe_dispatch(_kernel_label(fn), "fused",
                             time.perf_counter() - t0)
        fan = _FanOut(out, len(live), spec.check_finite,
                      valid_lens if spec.trim_outputs else None,
                      treedef_key=(fn, _statics_key(static_kw)))
        stats["dispatches"] = 1
    except Exception:  # noqa: BLE001 - degrade to per-member execution
        return _scalar_fallback(live, cancel_event, finish, stats,
                                overrides=overrides)

    for i, task in enumerate(live):
        if cancel_event.is_set():
            finish(task, -2)
            continue
        if not fan.ok[i]:
            finish(task, 1, exception=(
                "non-finite values in fused dispatch output "
                f"(member {task.name})"), n_live=len(live))
            continue
        finish(task, 0, result=fan.member(i), n_live=len(live))
        stats["fused"] += 1
    return stats


def _scalar_fallback(live: Sequence[Task], cancel_event: threading.Event,
                     finish, stats: Dict[str, int],
                     overrides: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, int]:
    """The batched dispatch raised (or could not be built): run each member
    on its own so only the actually-failing members fail."""
    for task in live:
        if cancel_event.is_set():
            finish(task, -2)
            continue
        try:
            fn, args, kwargs = member_call(task, overrides)
            t0 = time.perf_counter()
            result = fn(*args, **kwargs)
            tel.observe_dispatch(_kernel_label(fn), "scalar",
                                 time.perf_counter() - t0)
            spec = fusion_spec(fn)
            if (spec is not None and spec.check_finite
                    and hasattr(result, "dtype")
                    and np.issubdtype(np.asarray(result).dtype, np.floating)
                    and not np.isfinite(np.asarray(result)).all()):
                finish(task, 1, exception=(
                    f"non-finite values in scalar fallback output "
                    f"(member {task.name})"))
                continue
            finish(task, 0, result=result)
            stats["scalar_fallback"] += 1
        except Exception:  # noqa: BLE001 - per-member isolation
            finish(task, 1, exception=traceback.format_exc(limit=10))
    return stats


# --------------------------------------------------------------------------- #
# Chain execution (async: dispatch on the carrier worker, drain elsewhere)
# --------------------------------------------------------------------------- #

class ChainExecution:
    """One micro-batch of members through L chain links, asynchronously.

    The carrier worker calls :meth:`dispatch` — resolve, stack, enqueue the
    composed device dispatches, streaming one record per link — and
    returns. The RTS's completion drainer calls :meth:`drain`, which blocks
    on each link's device output in order and fans out the per-stage,
    per-member completions (the journal sees exactly the records a
    per-stage run would have produced, in the same order). A single-link
    "chain" is the plain PR-4 fused micro-batch, just asynchronous.

    Degrade ladder: a failed chain dispatch at link *k* falls back to
    per-stage fused execution of links *k..L-1* (consuming the carrier's
    own upstream values — never the store, which mid-chain may not have
    been routed yet), and a failed per-stage dispatch falls back to
    per-member scalar execution (inside :func:`execute_fused`). A sharded
    carrier (``mesh_devices``) enters the same ladder: any failure in the
    SPMD dispatch streams a degrade record and links re-run per-stage
    fused on a single device.
    """

    def __init__(self, links: Sequence[Sequence[Task]],
                 devices: Sequence[Any],
                 cancel_event: threading.Event,
                 deliver: Deliver,
                 *,
                 canceled: Optional[set] = None,
                 fault_injector: Optional[Callable[[Task], bool]] = None,
                 compose: bool = True,
                 mesh_devices: Optional[Sequence[Any]] = None) -> None:
        self.links: List[List[Task]] = [list(link) for link in links]
        self.compose = compose
        self.devices = devices
        self.cancel_event = cancel_event
        self.deliver = deliver
        self.canceled = canceled if canceled is not None else set()
        self.fault_injector = fault_injector
        self.started = time.time()
        self._mesh = build_mesh(mesh_devices)
        self.tier = ("shard" if self._mesh is not None
                     else "chain" if len(self.links) > 1 else "fused")
        self.stats = {"fused": 0, "scalar_fallback": 0, "failed": 0,
                      "dispatches": 0, "chain_links": 0,
                      "sharded_dispatches": 0, "degraded": 0}
        self._plans: List[Optional[_LinkPlan]] = [None] * len(self.links)
        self._injected: Dict[int, int] = {}   # member col -> first bad link
        self._fail_retryable: Dict[int, bool] = {}
        self._records: deque = deque()
        self._cv = threading.Condition()
        self._delivered: set = set()
        self._fail_link = 0

    # -- record stream ---------------------------------------------------- #

    def _push(self, record: Tuple) -> None:
        with self._cv:
            self._records.append(record)
            self._cv.notify_all()

    def _pop(self, stop_event: Optional[threading.Event]) -> Optional[Tuple]:
        with self._cv:
            while not self._records:
                if stop_event is not None and stop_event.is_set():
                    return None
                self._cv.wait(timeout=0.5)
            return self._records.popleft()

    # -- delivery --------------------------------------------------------- #

    def _finish(self, task: Task, exit_code: int, result: Any = None,
                exception: Optional[str] = None, n_live: int = 1,
                pilot_lost: bool = False) -> None:
        drop_member_call(task.uid)   # before the cancel skip: see finish()
        if task.uid in self.canceled or task.uid in self._delivered:
            return
        self._delivered.add(task.uid)
        now = time.time()
        if exit_code == 1 and not pilot_lost:
            self.stats["failed"] += 1
        self.deliver(TaskCompletion(
            uid=task.uid, exit_code=exit_code, result=result,
            exception=exception, started_at=self.started, completed_at=now,
            execution_seconds=(now - self.started) / max(1, n_live),
            pilot_lost=pilot_lost))

    # -- worker side ------------------------------------------------------ #

    def dispatch(self) -> None:
        """Resolve inputs, stack, and enqueue every link's device dispatch.

        Never raises: a preparation/dispatch failure at link *k* streams a
        ``degrade`` record so the drainer falls back for links *k..L-1*
        after fanning out the links that did dispatch.
        """
        try:
            if CARRIER_FAULT is not None and CARRIER_FAULT(self):
                raise RuntimeError("injected carrier fault (chaos plane)")
            self._dispatch_links()
        except Exception:  # noqa: BLE001 - drainer owns the fallback
            self._push(("degrade", self._fail_link,
                        traceback.format_exc(limit=10)))
        self._push(("end",))

    def _dispatch_links(self) -> None:
        if not self.links or not self.links[0]:
            return
        if self.cancel_event.is_set():
            self._push(("canceled",))
            return
        # fault injection is a per-member, per-link contract: the first
        # injected link fails the member there and poisons its downstream
        for k, tasks in enumerate(self.links):
            for m, t in enumerate(tasks):
                if (self.fault_injector is not None and m not in self._injected
                        and self.fault_injector(t)):
                    self._injected[m] = k
        if not self.compose and len(self.links) > 1:
            # composition declined (fusion_min_chain at the RTS): run the
            # links per-stage fused INSIDE the carrier — the carrier still
            # owns the ordering, so link k+1 never races link k's routing
            self._push(("degrade", 0, None))
            return
        self._fail_link = 0
        mesh = self._mesh
        with tel.span("fusion.prepare", "fusion") as prep:
            placed = None if prep is tel.NOOP_SPAN else []
            self._prepare_links(placed)
            if mesh is not None:
                self._place_plans(mesh, placed)
            if placed is not None:
                prep.set(placed_bytes=sum(placed), rows=self._rows())
        # dispatch: maximal runs of vmap-able links compose into ONE jitted
        # program; a hand-written batched impl executes eagerly between
        # segments (its jnp ops still enqueue asynchronously). Under a mesh
        # every dispatch is one shard_map program spanning all devices.
        idx = 0
        carry = None
        while idx < len(self._plans):
            self._fail_link = idx
            plan = self._plans[idx]
            if plan.spec.batched is not None:
                kw = dict(plan.stacked)
                if plan.carry_name is not None:
                    kw[plan.carry_name] = carry
                if mesh is not None:
                    out = self._sharded_batched(plan, kw)
                    self.stats["sharded_dispatches"] += 1
                else:
                    out = plan.spec.batched(**kw, **plan.static_kw,
                                            **plan.shared_kw)
                self.stats["dispatches"] += 1
                plan.t_dispatch = time.perf_counter()
                self._push(("link", idx, out))
                carry = out
                idx += 1
                continue
            j = idx
            while (j < len(self._plans)
                   and self._plans[j].spec.batched is None):
                j += 1
            segment = self._plans[idx:j]
            seg_fn = _composed_segment(segment, mesh=mesh)
            outs = seg_fn([p.stacked for p in segment],
                          [p.shared_kw for p in segment], carry)
            self.stats["dispatches"] += 1
            if mesh is not None:
                self.stats["sharded_dispatches"] += 1
            t_seg = time.perf_counter()
            for off, out in enumerate(outs):
                segment[off].t_dispatch = t_seg
                self._push(("link", idx + off, out))
            carry = outs[-1]
            idx = j

    def _prepare_links(self, placed: Optional[List[int]]) -> None:
        """Resolve and stack every link's members into its plan; a
        single-device carrier places them on its leased device."""
        entry_calls = [member_call(t) for t in self.links[0]]
        mesh = self._mesh
        # a sharded batch pads to n_shards equal pow2 shards so every mesh
        # device receives an identical block shape from the P('m') split
        entry_pad = None if mesh is None \
            else shard_pad(len(entry_calls), mesh.devices.size)
        device = None if mesh is not None else lease_device(self.devices)
        placed_here = placed if mesh is None else None
        fn, spec, static_kw, shared_kw, stacked, valid_lens, padded_b = \
            _prepare(entry_calls, pad_to=entry_pad, device=device,
                     placed=placed_here)
        self._plans[0] = _LinkPlan(self.links[0], fn, spec, static_kw,
                                   shared_kw, stacked, valid_lens, None)
        prev = self.links[0]
        inherited = valid_lens
        for j, tasks in enumerate(self.links[1:], start=1):
            self._fail_link = j
            calls, carry_name = _continuation_calls(tasks, prev)
            fnj, specj, st_kw, sh_kw, stk, vl, _ = _prepare(
                calls, pad_to=padded_b, device=device, placed=placed_here)
            if vl is None:
                # a padded axis rides the carry through the whole chain:
                # downstream links inherit the entry's per-member lengths so
                # their delivered values trim exactly like the scalar path's
                vl = inherited
            else:
                inherited = vl
            self._plans[j] = _LinkPlan(tasks, fnj, specj, st_kw, sh_kw, stk,
                                       vl, carry_name)
            prev = tasks

    def _rows(self) -> int:
        """Member rows stacked over the carrier's plans (unpadded)."""
        return sum(len(p.tasks) for p in self._plans if p is not None)

    @staticmethod
    def _count_mesh_placement(plan: _LinkPlan, mesh,
                              placed: Optional[List[int]]) -> None:
        """Bytes a mesh carrier moves for one plan: stacked kwargs split on
        the member axis, shared kwargs replicated (the program's call places
        those, as its in-specs ask)."""
        if placed is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        placed.append(_placed_nbytes(plan.stacked, NamedSharding(mesh, P("m")))
                      + _placed_nbytes(plan.shared_kw,
                                       NamedSharding(mesh, P())))

    def _place_plans(self, mesh, placed: Optional[List[int]] = None) -> None:
        """Place every link's stacked kwargs across the mesh member axis
        (shared kwargs replicate). Raises on unplaceable leaves — caught by
        :meth:`dispatch`, which degrades to the micro-batch ladder."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharded = NamedSharding(mesh, P("m"))
        for plan in self._plans:
            self._count_mesh_placement(plan, mesh, placed)
            plan.stacked = {k: jax.device_put(v, sharded)
                            for k, v in plan.stacked.items()}
            plan.shared_kw = jax.tree_util.tree_map(
                jnp.asarray, plan.shared_kw)

    def _sharded_batched(self, plan: _LinkPlan, kw: Dict[str, Any]) -> Any:
        """Run a hand-batched kernel under ``shard_map``: each mesh device
        invokes the kernel on its own member shard (the kernel's internal
        tiling — e.g. the Pallas grid — applies per shard)."""
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = self._mesh
        batched = plan.spec.batched
        static_kw = plan.static_kw

        def build():
            def call(kw_, sh_):
                return batched(**kw_, **sh_, **static_kw)
            return jax.jit(jax.shard_map(
                call, mesh=mesh, in_specs=(P("m"), P()),
                out_specs=P("m"), check_vma=False))

        cache_key = None if plan.statics_key is None else (
            "shard-batched", _mesh_key(mesh), batched, plan.statics_key,
            tuple(sorted(kw)), tuple(sorted(plan.shared_kw)))
        return _jit_cached(cache_key, build)(kw, plan.shared_kw)

    # -- drainer side ----------------------------------------------------- #

    def drain(self, stop_event: Optional[threading.Event] = None
              ) -> Dict[str, int]:
        """Block through the link records in order, fanning out per-stage,
        per-member completions; returns the accumulated statistics."""
        n = len(self.links[0]) if self.links and self.links[0] else 0
        ok = np.ones(n, bool)
        fail_reason: Dict[int, str] = {}
        # _fail_retryable: member col -> True when the failing link still
        # has retry budget. Its downstream links then requeue through the
        # pilot_lost channel (FAILED-without-budget-charge) instead of
        # failing permanently, so the upstream retry re-runs the member's
        # whole chain suffix — the outcome the per-stage gated path would
        # have produced.
        overrides: Dict[str, Any] = {}
        fanned = 0
        degraded = False
        while True:
            rec = self._pop(stop_event)
            if rec is None:        # RTS stopping: abandon without fabricating
                return self.stats
            kind = rec[0]
            if kind == "link":
                _, k, out = rec
                if degraded:
                    continue       # already handled by the fallback
                if not self._fan_link(k, out, ok, fail_reason, overrides):
                    degraded = True
                    fanned = len(self.links)
                else:
                    fanned = k + 1
            elif kind == "degrade":
                _, start, _exc = rec
                if not degraded:
                    if _exc is not None:
                        # a real dispatch failure (not a declined
                        # composition): the breaker board keys on this
                        self.stats["degraded"] += 1
                    start = max(start, fanned)
                    self._degrade(start, ok, fail_reason, overrides)
                    degraded = True
                    fanned = len(self.links)
            elif kind == "canceled":
                for tasks in self.links:
                    for t in tasks:
                        self._finish(t, -2)
                fanned = len(self.links)
            elif kind == "end":
                break
        if fanned < len(self.links):
            # the worker ended early without a degrade record (engine bug
            # guard): fall back for whatever never dispatched
            self._degrade(fanned, ok, fail_reason, overrides)
        return self.stats

    def _fan_link(self, k: int, out: Any, ok: np.ndarray,
                  fail_reason: Dict[int, str],
                  overrides: Dict[str, Any]) -> bool:
        """Resolve link ``k``'s output and fan it out; False ⇒ resolving
        failed (an async XLA error surfaced at transfer time) and the
        remaining links were degraded."""
        import jax

        plan = self._plans[k]
        tasks = self.links[k]
        n = len(tasks)
        try:
            out = jax.block_until_ready(out)
            if plan.t_dispatch is not None:
                tel.observe_dispatch(_kernel_label(plan.fn), self.tier,
                                     time.perf_counter() - plan.t_dispatch)
            fan = _FanOut(out, n, plan.spec.check_finite,
                          plan.valid_lens if plan.spec.trim_outputs else None,
                          treedef_key=(plan.fn, plan.statics_key))
        except Exception:  # noqa: BLE001 - degrade this link and the rest
            self.stats["degraded"] += 1
            self._degrade(k, ok, fail_reason, overrides)
            return False
        if len(self.links) > 1:
            self.stats["chain_links"] += 1
        for i, task in enumerate(tasks):
            if self.cancel_event.is_set():
                self._finish(task, -2)
                continue
            if not ok[i]:
                self._finish(task, 1, exception=fail_reason.get(
                    i, "upstream chain member failed"), n_live=n,
                    pilot_lost=self._fail_retryable.get(i, False))
                continue
            if self._injected.get(i) == k:
                ok[i] = False
                fail_reason[i] = (f"upstream chain member failed at link {k} "
                                  f"(injected fault)")
                self._fail_retryable[i] = task.retries < task.max_retries
                self._finish(task, 1, exception="injected fault", n_live=n)
                continue
            if not fan.ok[i]:
                ok[i] = False
                fail_reason[i] = (f"upstream chain member failed at link {k} "
                                  f"(non-finite output)")
                self._fail_retryable[i] = task.retries < task.max_retries
                self._finish(task, 1, exception=(
                    "non-finite values in fused dispatch output "
                    f"(member {task.name})"), n_live=n)
                continue
            value = fan.member(i)
            overrides[task.name] = value
            self._finish(task, 0, result=value, n_live=n)
            self.stats["fused"] += 1
        return True

    def _degrade(self, start: int, ok: np.ndarray,
                 fail_reason: Dict[int, str],
                 overrides: Dict[str, Any]) -> None:
        """Per-stage fused fallback for links ``start..L-1``, in link order,
        resolving carried inputs from ``overrides`` (this carrier's own
        fanned-out values) so the fallback can never race the store."""
        for k in range(start, len(self.links)):
            tasks = self.links[k]
            n = len(tasks)
            todo: List[Tuple[int, Task]] = []
            for i, task in enumerate(tasks):
                if self.cancel_event.is_set():
                    self._finish(task, -2)
                    continue
                if not ok[i]:
                    self._finish(task, 1, exception=fail_reason.get(
                        i, "upstream chain member failed"), n_live=n,
                        pilot_lost=self._fail_retryable.get(i, False))
                    continue
                if self._injected.get(i) == k:
                    ok[i] = False
                    fail_reason[i] = (f"upstream chain member failed at "
                                      f"link {k} (injected fault)")
                    self._fail_retryable[i] = \
                        task.retries < task.max_retries
                    self._finish(task, 1, exception="injected fault",
                                 n_live=n)
                    continue
                todo.append((i, task))
            if not todo:
                continue
            outcomes: Dict[str, TaskCompletion] = {}

            def dl(c: TaskCompletion) -> None:
                outcomes[c.uid] = c
                if c.uid in self.canceled or c.uid in self._delivered:
                    return
                self._delivered.add(c.uid)
                self.deliver(c)

            sub = execute_fused(
                [t for _, t in todo], self.devices, self.cancel_event, dl,
                canceled=self.canceled, fault_injector=None,
                overrides=overrides)
            for key in ("fused", "scalar_fallback", "failed", "dispatches"):
                self.stats[key] += sub.get(key, 0)
            for i, task in todo:
                c = outcomes.get(task.uid)
                if c is not None and c.exit_code == 0:
                    overrides[task.name] = c.result
                elif c is None or c.exit_code != -2:
                    ok[i] = False
                    fail_reason[i] = (f"upstream chain member failed at "
                                      f"link {k}")
                    self._fail_retryable[i] = \
                        task.retries < task.max_retries


# --------------------------------------------------------------------------- #
# DAG execution (fan-in reductions + fan-out broadcasts, one carrier)
# --------------------------------------------------------------------------- #

def _apply_reduction(stacked, mask, kind, combine, axis_name=None):
    """Masked device-side reduction of one ensemble node's stacked output.

    ``mask`` is the ``(B,)`` bool vector of live members known host-side
    (bucket/shard padding rows and injected faults); per-member finiteness
    is folded in HERE, in-program, so a poisoned member drops out of the
    reduction without a host sync — the survivors' reduction succeeds
    while the poisoned member fails alone at its own node's fan-out.

    Kinds reduce over EVERY axis of the valid members' values — the
    list-of-values semantics of ``np.sum([...])`` / ``np.max([...])`` —
    and an empty valid set yields NaN so the reduce task fails rather
    than fabricating an identity element. Under ``shard_map``
    (``axis_name``) each shard reduces locally and the partials combine
    across the mesh with ``psum``/``pmax``/``pmin``; the result is
    replicated on every device.
    """
    import jax
    import jax.numpy as jnp

    valid = jnp.asarray(mask)
    for leaf in jax.tree_util.tree_leaves(stacked):
        leaf = jnp.asarray(leaf)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            fin = jnp.isfinite(leaf.reshape(leaf.shape[0], -1)).all(axis=1)
            valid = valid & fin
    if combine is not None:
        return combine(stacked, valid)
    nvalid = jnp.sum(valid.astype(jnp.int32))
    if axis_name is not None:
        nvalid = jax.lax.psum(nvalid, axis_name)

    def red(leaf):
        leaf = jnp.asarray(leaf)
        m = valid.reshape((-1,) + (1,) * (leaf.ndim - 1))
        per_member = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
        if kind in ("sum", "mean"):
            total = jnp.sum(jnp.where(m, leaf, 0))
            if axis_name is not None:
                total = jax.lax.psum(total, axis_name)
            val = total / (nvalid * per_member) if kind == "mean" else total
        else:
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                neutral = jnp.inf if kind == "min" else -jnp.inf
            else:
                info = jnp.iinfo(leaf.dtype)
                neutral = info.max if kind == "min" else info.min
            val = (jnp.min if kind == "min" else jnp.max)(
                jnp.where(m, leaf, neutral))
            if axis_name is not None:
                val = (jax.lax.pmin if kind == "min" else jax.lax.pmax)(
                    val, axis_name)
        if jnp.issubdtype(jnp.result_type(val), jnp.floating):
            val = jnp.where(nvalid > 0, val, jnp.nan)
        return val

    return jax.tree_util.tree_map(red, stacked)


def _reduce_host(leaf) -> Any:
    """Host-side form of one reduced leaf: Python scalar for 0-d values
    (what a ``float(np.sum([...]))`` scalar reducer returns), ndarray
    otherwise."""
    arr = np.asarray(leaf)
    return arr.item() if arr.ndim == 0 else arr


def _dag_continuation_calls(tasks: Sequence[Task],
                            prev_tasks: Optional[Sequence[Task]],
                            carry_name: Optional[str],
                            bcast_name: Optional[str],
                            bcast_source: Optional[str]
                            ) -> List[Tuple[Callable, list, dict]]:
    """Resolve a DAG ensemble node's members WITHOUT touching the carried
    or broadcast inputs — both arrive device-resident inside the composed
    program. Unlike the chain's :func:`_continuation_calls` there is no
    inference: the compiler's tags name the edge kwargs, so the
    ``carry_name`` kwarg must hold the aligned previous member's future and
    the ``bcast_name`` kwarg the source reduction's future; any other
    future is foreign to the DAG and refuses composition."""
    from ..api.runtime import FUTURE_KEY
    from ..api.runtime import resolve as resolve_placeholders

    calls: List[Tuple[Callable, list, dict]] = []
    for i, t in enumerate(tasks):
        if t.executable != TRAMPOLINE:
            raise Incongruent("DAG node is not a data-flow task")
        if t.kwargs.get("__args__"):
            raise Incongruent("DAG node has positional args")
        ns = t.kwargs["__ns__"]
        fn = resolve_executable(t.kwargs["__fn__"])
        other: Dict[str, Any] = {}
        for k, v in (t.kwargs.get("__kwargs__") or {}).items():
            if isinstance(v, dict) and set(v) == {FUTURE_KEY}:
                name = v[FUTURE_KEY]
                if (carry_name is not None and k == carry_name
                        and prev_tasks is not None
                        and name == prev_tasks[i].name):
                    continue
                if (bcast_name is not None and k == bcast_name
                        and name == bcast_source):
                    continue
                raise Incongruent("DAG node consumes a foreign future")
            other[k] = _unwrap(resolve_placeholders(v, ns))
        calls.append((fn, [], other))
    return calls


class _DagNodeMeta:
    """Per-node routing parsed from the ``_fusion_dag`` tags: role, edge
    kwarg names, and the reduction semantics of ``"r"`` nodes (``combine``
    is resolved lazily at dispatch time from the reduce task's kernel)."""

    __slots__ = ("role", "carry_name", "bcast_name", "kind", "combine")

    def __init__(self, role: str, carry_name: Optional[str],
                 bcast_name: Optional[str], kind: Optional[str]) -> None:
        self.role = role
        self.carry_name = carry_name
        self.bcast_name = bcast_name
        self.kind = kind
        self.combine: Optional[Callable[..., Any]] = None


class DagExecution(ChainExecution):
    """One whole fused DAG — ``ensemble → then → gather → broadcast →
    ensemble`` — through one carrier, asynchronously.

    ``links`` holds one aligned task list per DAG *node* in node order:
    ensemble nodes their member tasks (width w), reduction nodes exactly
    one reduce task. Roles and edge kwargs come from the ``_fusion_dag``
    tags the compiler stamped. The dispatcher composes maximal runs of
    traceable nodes — ensemble nodes without a hand-batched impl, plus
    every reduction node — into single jitted programs threading the
    member-stacked ``carry`` and the replicated ``bcast`` (the last
    reduction's output) between nodes as XLA values; a hand-batched
    ensemble node executes eagerly between segments with both values
    staying device-resident. A diamond (``A → reduce → B`` with an
    elementwise ``A → B`` carry) therefore runs as ONE dispatch.

    Reductions execute masked (:func:`_apply_reduction`): padding and
    injected faults are excluded host-side, non-finite members in-program,
    so a poisoned member fails alone at its node while the reduction
    succeeds over the survivors; a reduction with NO live members (or a
    genuinely non-finite result) FAILS, and every downstream broadcast
    consumer fails with an upstream marker. On the sharded tier the same
    program runs under ``shard_map``: ensemble nodes stay split on the
    member axis, reductions combine shard partials with psum/pmax/pmin
    and come back replicated (out-spec ``P()``).

    Degrade ladder: any preparation or dispatch failure falls back to
    sequential per-node execution INSIDE the carrier — per-stage fused
    ensembles (then per-member scalar, inside :func:`execute_fused`) and
    *scalar* reductions resolving member values from the carrier's own
    overrides, with store-parity semantics: a scalar reduce over a failed
    member is a failed reduce, exactly like the un-fused gather path.
    """

    def __init__(self, links: Sequence[Sequence[Task]],
                 devices: Sequence[Any],
                 cancel_event: threading.Event,
                 deliver: Deliver,
                 *,
                 canceled: Optional[set] = None,
                 fault_injector: Optional[Callable[[Task], bool]] = None,
                 compose: bool = True,
                 mesh_devices: Optional[Sequence[Any]] = None) -> None:
        super().__init__(links, devices, cancel_event, deliver,
                         canceled=canceled, fault_injector=fault_injector,
                         compose=compose, mesh_devices=mesh_devices)
        self.tier = "dag-shard" if self._mesh is not None else "dag"
        self.stats["dag_links"] = 0
        self._meta: List[_DagNodeMeta] = []
        self._cols: List[List[int]] = []
        for tasks in self.links:
            tag = parse_dag_tag(tasks[0].tags) if tasks else None
            tag = tag or {}
            self._meta.append(_DagNodeMeta(
                tag.get("r", "e"), tag.get("a"), tag.get("b"),
                tag.get("rk")))
            # member COLUMN of each task: a resumed fragment's node list
            # can be partial, so list position and member index diverge —
            # per-member state (ok / injected / retryable) keys on the
            # tag's member index, which aligns columns across nodes
            cols = []
            for i, t in enumerate(tasks):
                tg = parse_dag_tag(t.tags)
                cols.append(tg["m"] if tg else i)
            self._cols.append(cols)
        self._masks: List[Optional[Any]] = [None] * len(self.links)
        self._injected_reduce: set = set()   # node index of injected "r"
        self._bcast_ok = True
        self._bcast_reason: Optional[str] = None
        self._bcast_retryable = False

    # -- worker side ------------------------------------------------------ #

    def _dispatch_links(self) -> None:
        if not self.links or not self.links[0]:
            return
        if self.cancel_event.is_set():
            self._push(("canceled",))
            return
        # injection: ensemble members key by member COLUMN (first injected
        # node wins, downstream poisons); a reduce node keys by NODE index
        # so its single task cannot collide with member 0's column
        for k, tasks in enumerate(self.links):
            if self._meta[k].role == "r":
                if (self.fault_injector is not None and tasks
                        and self.fault_injector(tasks[0])):
                    self._injected_reduce.add(k)
                continue
            for i, t in enumerate(tasks):
                col = self._cols[k][i]
                if (self.fault_injector is not None
                        and col not in self._injected
                        and self.fault_injector(t)):
                    self._injected[col] = k
        self._fail_link = 0
        if not self.compose:
            # composition declined (dag knob off at the RTS): sequential
            # per-node INSIDE the carrier — the carrier still owns the
            # ordering, so the reduce never races its members' routing
            self._push(("degrade", 0, None))
            return
        mesh = self._mesh
        with tel.span("fusion.prepare", "fusion") as prep:
            placed = None if prep is tel.NOOP_SPAN else []
            self._prepare_nodes(placed)
            if placed is not None:
                # the reduction masks are built on the host
                placed.append(_placed_nbytes(self._masks, None))
            if mesh is not None:
                self._place_dag(mesh, placed)
            if placed is not None:
                prep.set(placed_bytes=sum(placed), rows=self._rows())
        idx = 0
        carry = None
        bcast = None
        n = len(self.links)
        while idx < n:
            self._fail_link = idx
            meta = self._meta[idx]
            plan = self._plans[idx]
            if meta.role == "e" and plan.spec.batched is not None:
                kw = dict(plan.stacked)
                if meta.carry_name is not None:
                    kw[meta.carry_name] = carry
                if meta.bcast_name is not None:
                    plan.shared_kw = dict(plan.shared_kw)
                    plan.shared_kw[meta.bcast_name] = bcast
                if mesh is not None:
                    out = self._sharded_batched(plan, kw)
                    self.stats["sharded_dispatches"] += 1
                else:
                    out = plan.spec.batched(**kw, **plan.static_kw,
                                            **plan.shared_kw)
                self.stats["dispatches"] += 1
                plan.t_dispatch = time.perf_counter()
                self._push(("link", idx, out))
                carry = out
                idx += 1
                continue
            j = idx
            while j < n and not (self._meta[j].role == "e"
                                 and self._plans[j].spec.batched
                                 is not None):
                j += 1
            outs = self._dag_segment(idx, j, carry, bcast, mesh)
            self.stats["dispatches"] += 1
            if mesh is not None:
                self.stats["sharded_dispatches"] += 1
            t_seg = time.perf_counter()
            for off, out in enumerate(outs):
                if self._plans[idx + off] is not None:   # reduce: no plan
                    self._plans[idx + off].t_dispatch = t_seg
                self._push(("link", idx + off, out))
                if self._meta[idx + off].role == "e":
                    carry = out
                else:
                    bcast = out
            idx = j

    def _prepare_nodes(self, placed: Optional[List[int]] = None) -> None:
        """Build every node's plan, reduction mask and combine; raises
        :class:`Incongruent` on any unsupported shape — caught by
        :meth:`dispatch`, which degrades the WHOLE DAG to sequential
        per-node execution (prep happens before any dispatch)."""
        mesh = self._mesh
        placed_here = placed if mesh is None else None
        if self._meta[0].role != "e":
            raise Incongruent("DAG does not start at an ensemble node")
        if mesh is not None:
            widths = {len(t) for t, mt in zip(self.links, self._meta)
                      if mt.role == "e"}
            if len(widths) != 1:
                raise Incongruent("sharded DAG requires equal node widths")
        entry_calls = [member_call(t) for t in self.links[0]]
        entry_pad = None if mesh is None else shard_pad(
            len(entry_calls), mesh.devices.size)
        device = None if mesh is not None else lease_device(self.devices)
        fn, spec, static_kw, shared_kw, stacked, valid_lens, padded_b = \
            _prepare(entry_calls, pad_to=entry_pad, device=device,
                     placed=placed_here)
        self._plans[0] = _LinkPlan(self.links[0], fn, spec, static_kw,
                                   shared_kw, stacked, valid_lens, None)
        pad_of = {0: padded_b}       # e-node index -> padded batch axis
        lens_of = {0: valid_lens}    # e-node index -> row-pad lengths
        last_e = 0
        last_r_name: Optional[str] = None
        for k in range(1, len(self.links)):
            meta = self._meta[k]
            tasks = self.links[k]
            if meta.role == "r":
                if len(tasks) != 1:
                    raise Incongruent("reduction node must have one task")
                meta.combine = self._reduce_combine(k)
                if meta.combine is not None and mesh is not None:
                    raise Incongruent(
                        "custom combine cannot run under shard_map")
                if meta.combine is None and meta.kind is None:
                    raise Incongruent("reduction node lost its kind")
                if lens_of.get(last_e) is not None and (
                        meta.combine is not None
                        or meta.kind not in ("max", "min")):
                    # edge-replicated pad ROWS inside a member duplicate
                    # real values: harmless under max/min, wrong in a sum
                    raise Incongruent(
                        "row-padded member values only reduce safely "
                        "under max/min")
                self._masks[k] = self._node_mask(last_e, pad_of[last_e])
                last_r_name = tasks[0].name
                continue
            if meta.bcast_name is not None and last_r_name is None:
                raise Incongruent("broadcast precedes any reduction")
            calls = _dag_continuation_calls(
                tasks,
                self.links[last_e] if meta.carry_name is not None else None,
                meta.carry_name, meta.bcast_name, last_r_name)
            if (meta.carry_name is not None
                    and len(tasks) != len(self.links[last_e])):
                raise Incongruent("carry nodes disagree on member count")
            if meta.carry_name is not None:
                pad_to: Optional[int] = pad_of[last_e]
            else:
                pad_to = None if mesh is None else shard_pad(
                    len(tasks), mesh.devices.size)
            fnk, speck, st_kw, sh_kw, stk, vl, pb = _prepare(
                calls, pad_to=pad_to, device=device, placed=placed_here)
            if vl is None and meta.carry_name is not None:
                vl = lens_of[last_e]   # padded rows ride the carry through
            self._plans[k] = _LinkPlan(tasks, fnk, speck, st_kw, sh_kw,
                                       stk, vl, meta.carry_name)
            last_e = k
            pad_of[k] = pb
            lens_of[k] = vl

    def _reduce_combine(self, k: int) -> Optional[Callable[..., Any]]:
        task = self.links[k][0]
        if task.executable == TRAMPOLINE:
            fn = resolve_executable(task.kwargs["__fn__"])
        else:
            fn = task.resolve()
        spec = reduction_spec(fn)
        if spec is None:
            raise Incongruent("reduction node lost its fusable marker")
        return spec.combine

    def _node_mask(self, src: int, padded_b: int) -> np.ndarray:
        """Host-known live mask over the source node's padded member axis:
        bucket/shard pad rows off, injected members at or before the
        source node off (their poison reaches the reduced values)."""
        mask = np.zeros(padded_b, bool)
        for i, col in enumerate(self._cols[src]):
            k_inj = self._injected.get(col)
            mask[i] = k_inj is None or k_inj > src
        return mask

    def _place_dag(self, mesh, placed: Optional[List[int]] = None) -> None:
        """Place every ensemble node's stacked kwargs and every reduction
        mask across the mesh member axis (shared kwargs replicate)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharded = NamedSharding(mesh, P("m"))
        for k, plan in enumerate(self._plans):
            if plan is None:
                if self._masks[k] is not None:
                    self._masks[k] = jax.device_put(self._masks[k], sharded)
                continue
            self._count_mesh_placement(plan, mesh, placed)
            plan.stacked = {kk: jax.device_put(v, sharded)
                            for kk, v in plan.stacked.items()}
            plan.shared_kw = jax.tree_util.tree_map(
                jnp.asarray, plan.shared_kw)

    def _dag_segment(self, start: int, stop: int, carry, bcast, mesh):
        """Run nodes ``[start, stop)`` as one jitted program — ensemble
        nodes vmap, reduction nodes reduce — with carry and bcast threaded
        inside the program as XLA values (one dispatch for the run)."""
        import jax

        plans = [self._plans[k] for k in range(start, stop)]
        metas = self._meta[start:stop]
        stacked_list = [p.stacked for p, mt in zip(plans, metas)
                        if mt.role == "e"]
        shared_list = [p.shared_kw for p, mt in zip(plans, metas)
                       if mt.role == "e"]
        masks = [self._masks[k] for k in range(start, stop)
                 if self._meta[k].role == "r"]

        steps: List[Tuple] = []
        key_parts: Optional[List[Tuple]] = []
        for p, mt in zip(plans, metas):
            if mt.role == "e":
                steps.append(("e", p.fn, dict(p.static_kw), mt.carry_name,
                              mt.bcast_name))
                if key_parts is not None and p.statics_key is not None:
                    key_parts.append(
                        ("e", p.fn, p.statics_key, tuple(sorted(p.stacked)),
                         mt.carry_name, mt.bcast_name,
                         tuple(sorted(p.shared_kw))))
                else:
                    key_parts = None
            else:
                steps.append(("r", mt.kind, mt.combine))
                if key_parts is not None:
                    key_parts.append(("r", mt.kind, mt.combine))
        axis = None if mesh is None else "m"

        def seg(stacked_l, shared_l, masks_l, carry_, bcast_):
            outs = []
            si = mi = 0
            for step in steps:
                if step[0] == "e":
                    _, fn, static_kw, carry_name, bcast_name = step
                    kw = dict(stacked_l[si])
                    shb = shared_l[si]
                    si += 1
                    if carry_name is not None:
                        kw[carry_name] = carry_

                    def call(kw_, sh_, bc_, fn=fn, static_kw=static_kw,
                             bname=bcast_name):
                        if bname is not None:
                            kw_ = dict(kw_)
                            kw_[bname] = bc_
                        return fn(**kw_, **sh_, **static_kw)

                    out = jax.vmap(call, in_axes=(0, None, None))(
                        kw, shb, bcast_)
                    outs.append(out)
                    carry_ = out
                else:
                    _, kind, combine = step
                    out = _apply_reduction(carry_, masks_l[mi], kind,
                                           combine, axis_name=axis)
                    mi += 1
                    outs.append(out)
                    bcast_ = out
            return outs

        key = tuple(key_parts) if key_parts is not None else None
        if mesh is None:
            seg_fn = _jit_cached(("dag", key) if key else None,
                                 lambda: jax.jit(seg))
            return seg_fn(stacked_list, shared_list, masks, carry, bcast)

        from jax.sharding import PartitionSpec as P

        out_specs = [P("m") if mt.role == "e" else P() for mt in metas]

        def build():
            # check_vma=False: node kernels may contain pallas_call (no
            # replication rule); reductions come back replicated via the
            # in-program psum/pmax, which P() out-specs rely on
            return jax.jit(jax.shard_map(
                seg, mesh=mesh,
                in_specs=(P("m"), P(), P("m"), P("m"), P()),
                out_specs=out_specs, check_vma=False))

        seg_fn = _jit_cached(
            ("dag-shard", _mesh_key(mesh), key) if key else None, build)
        return seg_fn(stacked_list, shared_list, masks, carry, bcast)

    # -- drainer side ----------------------------------------------------- #

    def drain(self, stop_event: Optional[threading.Event] = None
              ) -> Dict[str, int]:
        """Chain drain loop over NODE records; member state is sized to the
        highest member column (widths change across a fan-in, and resumed
        fragments can hold sparse columns)."""
        width = max((max(c) + 1 for c in self._cols if c), default=1)
        ok = np.ones(width, bool)
        fail_reason: Dict[int, str] = {}
        overrides: Dict[str, Any] = {}
        fanned = 0
        degraded = False
        while True:
            rec = self._pop(stop_event)
            if rec is None:
                return self.stats
            kind = rec[0]
            if kind == "link":
                _, k, out = rec
                if degraded:
                    continue
                if not self._fan_node(k, out, ok, fail_reason, overrides):
                    degraded = True
                    fanned = len(self.links)
                else:
                    fanned = k + 1
            elif kind == "degrade":
                _, start, _exc = rec
                if not degraded:
                    if _exc is not None:
                        # a real dispatch failure (not a declined
                        # composition): the breaker board keys on this
                        self.stats["degraded"] += 1
                    start = max(start, fanned)
                    self._degrade(start, ok, fail_reason, overrides)
                    degraded = True
                    fanned = len(self.links)
            elif kind == "canceled":
                for tasks in self.links:
                    for t in tasks:
                        self._finish(t, -2)
                fanned = len(self.links)
            elif kind == "end":
                break
        if fanned < len(self.links):
            self._degrade(fanned, ok, fail_reason, overrides)
        return self.stats

    def _fan_node(self, k: int, out: Any, ok: np.ndarray,
                  fail_reason: Dict[int, str],
                  overrides: Dict[str, Any]) -> bool:
        if self._meta[k].role == "r":
            return self._fan_reduce(k, out, ok, fail_reason, overrides)
        import jax

        plan = self._plans[k]
        meta = self._meta[k]
        tasks = self.links[k]
        n = len(tasks)
        try:
            out = jax.block_until_ready(out)
            if plan.t_dispatch is not None:
                tel.observe_dispatch(_kernel_label(plan.fn), self.tier,
                                     time.perf_counter() - plan.t_dispatch)
            fan = _FanOut(out, n, plan.spec.check_finite,
                          plan.valid_lens if plan.spec.trim_outputs else None,
                          treedef_key=(plan.fn, plan.statics_key))
        except Exception:  # noqa: BLE001 - degrade this node and the rest
            self.stats["degraded"] += 1
            self._degrade(k, ok, fail_reason, overrides)
            return False
        self.stats["dag_links"] += 1
        bcast_bad = meta.bcast_name is not None and not self._bcast_ok
        has_carry = meta.carry_name is not None
        for i, task in enumerate(tasks):
            col = self._cols[k][i]
            if self.cancel_event.is_set():
                self._finish(task, -2)
                continue
            if bcast_bad:
                ok[col] = False
                fail_reason[col] = (self._bcast_reason
                                    or "upstream DAG reduction failed")
                self._finish(task, 1, exception=fail_reason[col], n_live=n,
                             pilot_lost=self._bcast_retryable)
                continue
            if has_carry and not ok[col]:
                self._finish(task, 1, exception=fail_reason.get(
                    col, "upstream DAG member failed"), n_live=n,
                    pilot_lost=self._fail_retryable.get(col, False))
                continue
            if self._injected.get(col) == k:
                ok[col] = False
                fail_reason[col] = (f"upstream DAG member failed at node "
                                    f"{k} (injected fault)")
                self._fail_retryable[col] = task.retries < task.max_retries
                self._finish(task, 1, exception="injected fault", n_live=n)
                continue
            if not fan.ok[i]:
                ok[col] = False
                fail_reason[col] = (f"upstream DAG member failed at node "
                                    f"{k} (non-finite output)")
                self._fail_retryable[col] = task.retries < task.max_retries
                self._finish(task, 1, exception=(
                    "non-finite values in fused dispatch output "
                    f"(member {task.name})"), n_live=n)
                continue
            # explicit True: a node WITHOUT a carry starts a fresh member
            # lineage — an earlier failure in a dead lineage must not leak
            ok[col] = True
            value = fan.member(i)
            overrides[task.name] = value
            self._finish(task, 0, result=value, n_live=n)
            self.stats["fused"] += 1
        return True

    def _fan_reduce(self, k: int, out: Any, ok: np.ndarray,
                    fail_reason: Dict[int, str],
                    overrides: Dict[str, Any]) -> bool:
        import jax

        task = self.links[k][0]
        plan = self._plans[k]
        try:
            out = jax.block_until_ready(out)
            if plan is not None and plan.t_dispatch is not None:
                tel.observe_dispatch(_kernel_label(plan.fn), self.tier,
                                     time.perf_counter() - plan.t_dispatch)
            value = jax.tree_util.tree_map(_reduce_host, out)
        except Exception:  # noqa: BLE001 - degrade this node and the rest
            self.stats["degraded"] += 1
            self._degrade(k, ok, fail_reason, overrides)
            return False
        self.stats["dag_links"] += 1
        if self.cancel_event.is_set():
            self._finish(task, -2)
            return True
        if k in self._injected_reduce:
            self._set_bcast_bad(k, task, "injected fault")
            self._finish(task, 1, exception="injected fault")
            return True
        finite = all(
            np.isfinite(np.asarray(leaf)).all()
            for leaf in jax.tree_util.tree_leaves(value)
            if np.issubdtype(np.asarray(leaf).dtype, np.floating))
        if not finite:
            msg = (f"fused reduction produced non-finite values at node "
                   f"{k} (poisoned inputs or no live members)")
            self._set_bcast_bad(k, task, msg)
            self._finish(task, 1, exception=msg)
            return True
        self._bcast_ok = True      # a later reduction refreshes the bcast
        self._bcast_retryable = False
        overrides[task.name] = value
        self._finish(task, 0, result=value)
        self.stats["fused"] += 1
        return True

    def _set_bcast_bad(self, k: int, task: Task, msg: str) -> None:
        self._bcast_ok = False
        self._bcast_reason = (f"upstream DAG reduction failed at node {k}: "
                              f"{msg}")
        self._bcast_retryable = task.retries < task.max_retries

    def _degrade(self, start: int, ok: np.ndarray,
                 fail_reason: Dict[int, str],
                 overrides: Dict[str, Any]) -> None:
        """Sequential per-node fallback for nodes ``start..N-1``, in node
        order inside the carrier: ensemble nodes per-stage fused (then
        per-member scalar inside :func:`execute_fused`), reduction nodes
        SCALAR — resolving member values from the carrier's own overrides
        first, then the store, so a failed member makes the reduce fail
        exactly like the un-fused gather path."""
        for k in range(start, len(self.links)):
            meta = self._meta[k]
            if meta.role == "r":
                self._degrade_reduce(k, overrides)
                continue
            tasks = self.links[k]
            n = len(tasks)
            bcast_bad = meta.bcast_name is not None and not self._bcast_ok
            has_carry = meta.carry_name is not None
            todo: List[Tuple[int, Task]] = []
            for i, task in enumerate(tasks):
                col = self._cols[k][i]
                if self.cancel_event.is_set():
                    self._finish(task, -2)
                    continue
                if bcast_bad:
                    ok[col] = False
                    fail_reason[col] = (self._bcast_reason
                                        or "upstream DAG reduction failed")
                    self._finish(task, 1, exception=fail_reason[col],
                                 n_live=n, pilot_lost=self._bcast_retryable)
                    continue
                if has_carry and not ok[col]:
                    self._finish(task, 1, exception=fail_reason.get(
                        col, "upstream DAG member failed"), n_live=n,
                        pilot_lost=self._fail_retryable.get(col, False))
                    continue
                if self._injected.get(col) == k:
                    ok[col] = False
                    fail_reason[col] = (f"upstream DAG member failed at "
                                        f"node {k} (injected fault)")
                    self._fail_retryable[col] = \
                        task.retries < task.max_retries
                    self._finish(task, 1, exception="injected fault",
                                 n_live=n)
                    continue
                todo.append((col, task))
            if not todo:
                continue
            outcomes: Dict[str, TaskCompletion] = {}

            def dl(c: TaskCompletion) -> None:
                outcomes[c.uid] = c
                if c.uid in self.canceled or c.uid in self._delivered:
                    return
                self._delivered.add(c.uid)
                self.deliver(c)

            sub = execute_fused(
                [t for _, t in todo], self.devices, self.cancel_event, dl,
                canceled=self.canceled, fault_injector=None,
                overrides=overrides)
            for key in ("fused", "scalar_fallback", "failed", "dispatches"):
                self.stats[key] += sub.get(key, 0)
            for col, task in todo:
                c = outcomes.get(task.uid)
                if c is not None and c.exit_code == 0:
                    ok[col] = True
                    overrides[task.name] = c.result
                elif c is None or c.exit_code != -2:
                    ok[col] = False
                    fail_reason[col] = (f"upstream DAG member failed at "
                                        f"node {k}")
                    self._fail_retryable[col] = \
                        task.retries < task.max_retries

    def _degrade_reduce(self, k: int, overrides: Dict[str, Any]) -> None:
        task = self.links[k][0]
        if self.cancel_event.is_set():
            self._finish(task, -2)
            return
        if k in self._injected_reduce:
            self._set_bcast_bad(k, task, "injected fault")
            self._finish(task, 1, exception="injected fault")
            return
        try:
            fn, args, kwargs = member_call(task, overrides)
            value = fn(*args, **kwargs)
        except Exception:  # noqa: BLE001 - store-parity: missing member
            self._set_bcast_bad(k, task,
                                f"scalar reduction failed at node {k}")
            self._finish(task, 1,
                         exception=traceback.format_exc(limit=10))
            return
        self._bcast_ok = True
        self._bcast_retryable = False
        overrides[task.name] = value
        self._finish(task, 0, result=value)
        self.stats["scalar_fallback"] += 1
