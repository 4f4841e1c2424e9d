"""Telemetry plane tests: tracer exactness under concurrency, bounded-ring
overflow accounting, metric registry thread-safety and in-place reset,
streaming quantiles, Chrome-trace round-trip, and end-to-end span/metric
capture from a fused run; the adaptive loop's hook spans, the fusion
engine's ``fusion.prepare`` span and its placed bytes, and the mirror of
every span into a JAX profiler trace."""

import json
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import numpy as np
import pytest

from repro import api, telemetry
from repro.fusion import fusable, fusable_reduction
from repro.rts.base import ResourceDescription
from repro.rts.jax_rts import JaxRTS
from repro.telemetry import (DISPATCH_LATENCY, MetricsRegistry, SpanTracer,
                             NOOP_SPAN)

ROOT = Path(__file__).resolve().parents[1]


@fusable(static_argnames=("scale",))
def k_tel(x, scale=1.0):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) * scale


@pytest.fixture
def tracing():
    """Enable tracing for one test; restore the disabled default after."""
    telemetry.enable()
    telemetry.TRACER.clear()
    yield
    telemetry.disable()
    telemetry.TRACER.clear()


# --------------------------------------------------------------------------- #
# Zero-cost-when-off contract
# --------------------------------------------------------------------------- #

def test_disabled_span_is_noop_singleton():
    telemetry.disable()
    s = telemetry.span("anything", "cat", a=1)
    assert s is NOOP_SPAN
    assert s.set(b=2) is NOOP_SPAN            # chainable, allocates nothing
    with s:
        pass
    s.end()
    assert len(telemetry.TRACER) == 0         # nothing was recorded
    telemetry.event("nothing")                # events gated too
    assert len(telemetry.TRACER) == 0


def test_metrics_live_even_when_tracing_off():
    telemetry.disable()
    c = telemetry.counter("tel_test_counter", probe="live")
    before = c.value
    c.inc(3)
    assert c.value == before + 3


# --------------------------------------------------------------------------- #
# Tracer: nesting, concurrency, ring overflow
# --------------------------------------------------------------------------- #

def test_nested_spans_record_depth_and_attrs(tracing):
    with telemetry.span("outer", "t", k="v"):
        with telemetry.span("inner", "t") as inner:
            inner.set(extra=7)
    recs = {r["name"]: r for r in telemetry.TRACER.snapshot()}
    assert recs["outer"]["depth"] == 0 and recs["outer"]["attrs"] == {"k": "v"}
    assert recs["inner"]["depth"] == 1 and recs["inner"]["attrs"] == {"extra": 7}
    assert recs["inner"]["dur"] <= recs["outer"]["dur"]


def test_concurrent_begin_end_is_exact():
    tracer = SpanTracer(ring_size=100_000)
    threads, per_thread = 8, 200

    def work():
        for _ in range(per_thread):
            outer = tracer.begin("outer")
            inner = tracer.begin("inner")
            inner.end()
            outer.end()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = tracer.snapshot()
    assert len(recs) == threads * per_thread * 2
    assert tracer.dropped_spans == 0
    # per-thread nesting is exact: every inner sits at depth 1, every
    # outer at depth 0, regardless of cross-thread interleaving
    for r in recs:
        assert r["depth"] == (1 if r["name"] == "inner" else 0)


def test_ring_overflow_drops_oldest_and_counts():
    tracer = SpanTracer(ring_size=10)
    for i in range(25):
        tracer.begin("s", i=i).end()
    recs = tracer.snapshot()
    assert len(recs) == 10
    assert tracer.dropped_spans == 15
    # oldest-first snapshot holds exactly the newest ten
    assert [r["attrs"]["i"] for r in recs] == list(range(15, 25))
    tracer.clear()
    assert len(tracer) == 0 and tracer.dropped_spans == 0


def test_span_end_is_idempotent(tracing):
    s = telemetry.span("once", "t")
    s.end()
    s.end()
    assert sum(1 for r in telemetry.TRACER.snapshot()
               if r["name"] == "once") == 1


# --------------------------------------------------------------------------- #
# Metrics: registry exactness, quantiles, reset-in-place
# --------------------------------------------------------------------------- #

def test_counter_exact_under_contention():
    reg = MetricsRegistry()
    threads, per_thread = 8, 5_000

    def work():
        # re-fetch the handle each time: memoization must hand every
        # thread the same locked cell (the fusion_stats race this fixes)
        for _ in range(per_thread):
            reg.counter("hits", where="hot").inc()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("hits", where="hot").value == threads * per_thread


def test_histogram_quantiles_bounded_error():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    values = [i / 1000.0 for i in range(1, 1001)]     # 1ms .. 1s uniform
    for v in values:
        h.observe(v)
    q = h.quantiles()
    # log-bucketed streaming estimate: <=5% relative bucket error
    assert q["p50"] == pytest.approx(0.5, rel=0.06)
    assert q["p90"] == pytest.approx(0.9, rel=0.06)
    assert q["p99"] == pytest.approx(0.99, rel=0.06)
    s = h.summary()
    assert s["count"] == 1000 and s["min"] == 0.001 and s["max"] == 1.0


def test_quantiles_merge_across_tiers_per_kernel():
    reg = MetricsRegistry()
    for v in (0.010, 0.011, 0.012):
        reg.histogram(DISPATCH_LATENCY, kernel="k", tier="fused").observe(v)
    for v in (0.020, 0.021):
        reg.histogram(DISPATCH_LATENCY, kernel="k", tier="scalar").observe(v)
    reg.histogram(DISPATCH_LATENCY, kernel="other", tier="fused").observe(9.0)
    merged = reg.quantiles("k")
    assert merged["count"] == 5
    assert 0.010 <= merged["p50"] <= 0.021
    narrowed = reg.quantiles("k", tier="scalar")
    assert narrowed["count"] == 2
    assert reg.kernels() == ["k", "other"]


def test_registry_reset_zeroes_in_place():
    reg = MetricsRegistry()
    c = reg.counter("c")
    h = reg.histogram("h")
    c.inc(5)
    h.observe(1.0)
    reg.reset()
    # the SAME handles keep working — module-cached handles survive reset
    assert c.value == 0 and h.count == 0
    c.inc()
    assert reg.counter("c").value == 1


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("jobs_total", tenant="a").inc(2)
    reg.gauge("depth").set(3.5)
    reg.histogram("lat", kernel="k").observe(0.25)
    text = reg.prometheus_text()
    assert "# TYPE jobs_total counter" in text
    assert 'jobs_total{tenant="a"} 2' in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat summary" in text
    assert 'lat_count{kernel="k"} 1' in text
    assert 'quantile="0.5"' in text


# --------------------------------------------------------------------------- #
# Chrome-trace export round-trip
# --------------------------------------------------------------------------- #

def test_chrome_trace_roundtrip(tracing, tmp_path):
    with telemetry.span("work", "test", tier="fused", members=4):
        telemetry.event("tick", "test", n=1)
    path = tmp_path / "trace.json"
    telemetry.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    work = [e for e in events if e["name"] == "work"]
    assert work and work[0]["ph"] == "X" and work[0]["dur"] >= 0
    assert work[0]["args"] == {"tier": "fused", "members": 4}
    ticks = [e for e in events if e["name"] == "tick"]
    assert ticks and ticks[0]["ph"] == "i" and ticks[0]["s"] == "t"
    # thread-name metadata labels the track
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)
    assert doc["otherData"]["dropped_spans"] == 0
    assert "metrics" in doc["otherData"]


def test_jsonl_export_roundtrip(tracing, tmp_path):
    telemetry.counter("tel_jsonl_probe").inc()
    path = tmp_path / "telemetry.jsonl"
    telemetry.export_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["kind"] == "meta"
    assert any(r.get("name") == "tel_jsonl_probe" for r in lines[1:])


# --------------------------------------------------------------------------- #
# End-to-end: a fused run leaves spans with tier attrs + kernel quantiles
# --------------------------------------------------------------------------- #

def test_fused_run_emits_carrier_spans_and_kernel_quantiles(tracing):
    telemetry.REGISTRY.reset()
    ens = api.ensemble(k_tel, over=[{"x": float(i), "scale": 2.0}
                                    for i in range(8)], name="tel-e2e")
    res = api.run(ens, resources=ResourceDescription(slots=4),
                  rts_factory=lambda: JaxRTS(devices=["d0"],
                                             slot_oversubscribe=4),
                  timeout=60)
    vals = [float(np.asarray(s.out.result())) for s in ens.specs]
    assert vals == [2.0 * i for i in range(8)]
    assert res is not None

    dispatch = [r for r in telemetry.TRACER.snapshot()
                if r["name"] == "carrier.dispatch"]
    assert dispatch, "fused run recorded no carrier.dispatch spans"
    attrs = dispatch[0]["attrs"]
    assert attrs["tier"] in ("fused", "chain", "dag", "shard")
    assert attrs["members"] >= 1 and attrs["width"] >= 1
    assert "tenants" in attrs

    # acceptance: per-kernel latency quantiles are queryable by name
    assert "k_tel" in telemetry.kernels()
    q = telemetry.quantiles("k_tel")
    assert q["count"] >= 1
    assert q["p50"] is not None and q["p99"] is not None
    assert q["p50"] <= (q["p99"] or float("inf"))


# --------------------------------------------------------------------------- #
# The adaptive loop's hooks, the engine's input preparation, the profiler
# mirror
# --------------------------------------------------------------------------- #

@fusable()
def k_round(x):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) * 2.0


def _loop_run(name, predicate=None, rounds=3):
    """A small ``repeat_until`` on the fused tier: ``rounds`` rounds of four
    members on the real CPU device."""
    import jax

    def body(ctx):
        return api.ensemble(
            k_round, over=[{"x": np.full(4, ctx.round + i, np.float32)}
                           for i in range(4)], name=f"{name}-b{ctx.round}")

    loop = api.repeat_until(
        predicate or (lambda ctx: ctx.round + 1 >= rounds), body, name=name,
        max_rounds=rounds + 2)
    return api.run(loop, resources=ResourceDescription(slots=4),
                   rts_factory=lambda: JaxRTS(devices=jax.devices(),
                                              slot_oversubscribe=4),
                   name=f"{name}-wf", timeout=60)


def _parent(rec, recs):
    """The span ``rec`` sits directly inside (same thread, one level up)."""
    end = rec["ts"] + rec["dur"]
    for p in recs:
        if (p["tid"] == rec["tid"] and p["depth"] == rec["depth"] - 1
                and p["ts"] <= rec["ts"] and end <= p["ts"] + p["dur"]):
            return p
    return None


def test_loop_hooks_are_spans_inside_the_dequeue_batch(tracing):
    res = _loop_run("hk")
    assert res.all_done
    recs = [r for r in telemetry.TRACER.snapshot() if r["ph"] == "X"]
    preds = [r for r in recs if r["name"] == "api.loop.predicate"]
    bodies = [r for r in recs if r["name"] == "api.loop.body"]
    assert [r["attrs"] for r in preds] == [
        {"loop": "hk", "round": k} for k in range(3)]
    assert [r["attrs"] for r in bodies] == [
        {"loop": "hk", "round": k} for k in range(3)]
    # round 0 is built at compile time, on the caller's thread
    assert bodies[0]["depth"] == 0 and _parent(bodies[0], recs) is None
    assert bodies[0]["thread"] != preds[0]["thread"]
    # every later hook is a direct child of the WFProcessor's dequeue batch
    for r in preds + bodies[1:]:
        parent = _parent(r, recs)
        assert parent is not None and parent["name"] == "wfp.dequeue_batch"


def test_raising_predicate_still_closes_its_span(tracing):
    def bad(ctx):
        raise RuntimeError("boom in predicate")

    with pytest.raises(Exception, match="adaptive hook"):
        _loop_run("hkx", predicate=bad)
    recs = [r for r in telemetry.TRACER.snapshot() if r["ph"] == "X"]
    [pred] = [r for r in recs if r["name"] == "api.loop.predicate"]
    assert pred["attrs"] == {"loop": "hkx", "round": 0}
    parent = _parent(pred, recs)
    assert parent is not None and parent["name"] == "wfp.dequeue_batch"
    # the thread's nesting is whole again: its later spans sit at the top
    later = [r for r in recs if r["tid"] == pred["tid"]
             and r["ts"] > parent["ts"] + parent["dur"]]
    assert all(r["depth"] == 0 for r in later)


_PLACE_W = {}


@fusable(operands=lambda: {"w": _PLACE_W["w"]})
def k_place(x, w):
    import jax.numpy as jnp
    return jnp.asarray(x, jnp.float32) * w


@fusable_reduction(kind="sum")
def r_place(values):
    return np.sum([np.asarray(v) for v in values], axis=0)


def _prepare_spans(name, xs, w, reduce=False):
    """``fusion.prepare`` records of one fused run of ``k_place``."""
    import jax

    _PLACE_W["w"] = w
    telemetry.TRACER.clear()
    node = api.ensemble(k_place, over=[{"x": x} for x in xs], name=name)
    if reduce:
        node = api.gather(node, r_place, name=f"{name}-sum")
    res = api.run(node, resources=ResourceDescription(slots=4),
                  rts_factory=lambda: JaxRTS(devices=jax.devices(),
                                             slot_oversubscribe=4),
                  timeout=60)
    assert res.all_done
    res.close()
    return [r for r in telemetry.TRACER.snapshot()
            if r["name"] == "fusion.prepare"]


@pytest.mark.parametrize("reduce", [False, True], ids=["fused", "dag"])
def test_fusion_prepare_counts_the_bytes_placement_moves(tracing, reduce):
    import jax.numpy as jnp

    host = [np.full(16, i, np.float32) for i in range(4)]
    [rec] = _prepare_spans("pl-host", host, np.ones(16, np.float32), reduce)
    masks = 4 if reduce else 0            # one host bool per member row
    assert rec["attrs"] == {"placed_bytes": 4 * 64 + 64 + masks, "rows": 4}
    assert rec["depth"] == 1              # inside carrier.dispatch
    resident = [jnp.full(16, i, jnp.float32) for i in range(4)]
    [rec] = _prepare_spans("pl-dev", resident, jnp.ones(16, jnp.float32),
                           reduce)
    assert rec["attrs"] == {"placed_bytes": masks, "rows": 4}


def test_fusion_prepare_counts_a_mesh_carrier_once_per_input():
    """On four virtual CPU devices: stacked host rows are placed, an
    operand on one device is replicated (counted once), and an operand
    already on every device moves nothing."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_PROBE)],
        capture_output=True, text=True, timeout=240, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    one, everywhere = json.loads(proc.stdout.strip().splitlines()[-1])
    for attrs in (one, everywhere):
        assert attrs["tier"] == "shard" and attrs["rows"] == 8
    assert one["placed_bytes"] == 8 * 64 + 64
    assert everywhere["placed_bytes"] == 8 * 64


_MESH_PROBE = """
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api, telemetry
    from repro.fusion import fusable
    from repro.fusion.plans import MeshPlan
    from repro.rts.base import ResourceDescription
    from repro.rts.jax_rts import JaxRTS

    W = {}

    @fusable(operands=lambda: {"w": W["w"]})
    def k_mesh(x, w):
        return jnp.asarray(x, jnp.float32) * w

    def factory():
        rts = JaxRTS(slot_oversubscribe=1)
        rts._plan_mesh = lambda n, free, ms, tags: MeshPlan(
            n_shards=4, batches=[n])
        return rts

    def prepare(name, w):
        W["w"] = w
        telemetry.TRACER.clear()
        ens = api.ensemble(k_mesh, over=[{"x": np.full(16, i, np.float32)}
                                         for i in range(8)], name=name)
        res = api.run(ens, resources=ResourceDescription(slots=4),
                      rts_factory=factory, timeout=120)
        assert res.all_done
        res.close()
        recs = telemetry.TRACER.snapshot()
        [disp] = [r for r in recs if r["name"] == "carrier.dispatch"]
        [prep] = [r for r in recs if r["name"] == "fusion.prepare"]
        return dict(prep["attrs"], tier=disp["attrs"]["tier"])

    telemetry.enable()
    mesh = Mesh(np.array(jax.devices()), ("m",))
    everywhere = jax.device_put(jnp.ones(16, jnp.float32),
                                NamedSharding(mesh, P()))
    print(json.dumps([prepare("one", jnp.ones(16, jnp.float32)),
                      prepare("everywhere", everywhere)]))
"""


def test_new_sites_are_noop_with_tracing_off(monkeypatch):
    from repro.fusion import engine

    telemetry.disable()
    telemetry.TRACER.clear()
    opened = []
    real_span = telemetry.span

    def spy(name, cat="", **attrs):
        s = real_span(name, cat, **attrs)
        opened.append((name, s))
        return s

    def no_count(*a, **k):
        raise AssertionError("placement counted with tracing off")

    monkeypatch.setattr(telemetry, "span", spy)
    monkeypatch.setattr(engine, "_placed_nbytes", no_count)
    assert _loop_run("hkoff", rounds=2).all_done
    names = {n for n, _ in opened}
    assert {"api.loop.predicate", "api.loop.body",
            "fusion.prepare"} <= names
    assert all(s is NOOP_SPAN for _, s in opened)
    assert len(telemetry.TRACER) == 0


def test_the_mirror_binds_only_where_jax_is_imported():
    import jax

    telemetry.enable()
    try:
        assert telemetry.TRACER.mirror is jax.profiler.TraceAnnotation
    finally:
        telemetry.disable()
    assert telemetry.TRACER.mirror is None
    code = ("import sys; from repro import telemetry; telemetry.enable(); "
            "assert telemetry.TRACER.mirror is None; "
            "assert 'jax' not in sys.modules")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60, env=env)
    assert proc.returncode == 0, proc.stderr


def test_profiler_trace_holds_every_program_span(tmp_path):
    """A JAX profiler trace taken with telemetry on holds every program
    span on the host plane: same name and nesting, the same duration, and
    one constant offset to the records' monotonic clock."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    telemetry.enable()
    telemetry.TRACER.clear()
    try:
        assert _loop_run("mir").all_done
        handoff = telemetry.span("handoff", "test")
        t = threading.Thread(target=handoff.end)
        t.start()
        t.join()
    finally:
        telemetry.disable()
        jax.profiler.stop_trace()
    recs = [r for r in telemetry.TRACER.snapshot() if r["ph"] == "X"]
    names = {r["name"] for r in recs}
    assert {"api.loop.predicate", "api.loop.body", "fusion.prepare",
            "carrier.dispatch", "wfp.dequeue_batch"} <= names

    [path] = list(tmp_path.glob("**/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    events = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    events.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    # a span ended on another thread than its own is not mirrored
    assert "handoff" not in events
    mirrored = {}
    for name in names - {"handoff"}:
        mine = sorted((r for r in recs if r["name"] == name),
                      key=lambda r: r["ts"])
        theirs = sorted(events.get(name, []))
        assert len(theirs) == len(mine), name
        for r, ev in zip(mine, theirs):
            mirrored[id(r)] = ev
    offsets = [r["ts"] - mirrored[id(r)][0] for r in recs
               if id(r) in mirrored]
    assert max(offsets) - min(offsets) <= 200_000
    for r in recs:
        if id(r) not in mirrored:
            continue
        start, end = mirrored[id(r)]
        assert abs((end - start) - r["dur"]) <= 200_000, r["name"]
        parent = _parent(r, recs)
        if parent is not None:
            p_start, p_end = mirrored[id(parent)]
            assert p_start <= start and end <= p_end, (r["name"],
                                                       parent["name"])
