"""Observability subsystem.

Covers:
  * the span tracer: recording, Chrome trace-event export schema
    (``validate_chrome_trace`` both accepts real traces and rejects broken
    ones), bounded ring buffer, a *true* no-op when disabled —
    an engine run with a disabled tracer records zero events — and spans
    that land on a JAX profiler trace's clock,
  * where the engine and the stream server record: only on the engine's
    threads (nested ``h2d``/``launch``, ``handoff``, per-batch request
    records, ``coalesce`` from the first request taken) and around each
    ``push``; per-segment named scopes in the executor's HLO,
  * the metrics registry: counters/gauges/histograms, kind safety, JSON
    snapshots, and the executor-cache / cache_fifo wiring,
  * ``ServeStats``: the cross-thread race fix (locked snapshot) and the
    documented empty-window / single-sample ``latency_ms`` contract,
  * the pipeline-overlap design claim from the serving PR: under a burst,
    the ``stage`` span of batch k+1 overlaps the ``device`` span of batch
    k (double buffering, previously untested),
  * the static cost model: ds_cnn MACs re-derived by hand layer-for-layer
    must equal the report total; the arena timeline's independently-derived
    peak must equal the planner's arena bytes for every workload × dtype,
  * the per-segment device-timing mode and the report CLI (smoke).
"""
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer, validate_chrome_trace
from repro.obs import report
from repro.serve.cnn_engine import CNNEngine, CoalescePolicy, ServeStats
from repro.serve.step import BucketedExecutorCache


@pytest.fixture(scope="module")
def lenet_bundle():
    return report.build_workload("lenet")


@pytest.fixture(scope="module")
def lenet_engine_parts(lenet_bundle):
    b = lenet_bundle
    return b["graph"], b["plan"], b["params"]


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_records_and_exports_valid_chrome_trace():
    tr = Tracer(process_name="t")
    tr.name_thread("main")
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    tr.counter("depth", depth=3)
    tr.instant("mark")
    t = time.perf_counter()
    tr.requests([7, 8], [tr.perf_ns(t), tr.perf_ns(t + 0.001)],
                tr.perf_ns(t + 0.002), tr.perf_ns(t + 0.003), batch=0)
    trace = tr.export()
    validate_chrome_trace(trace)
    names = [e["name"] for e in trace["traceEvents"]]
    assert {"outer", "inner", "depth", "mark", "request"} <= set(names)
    # one async request span per id, its lane on the end event
    ends = {e["id"]: e for e in trace["traceEvents"] if e["ph"] == "e"}
    assert ends["7"]["args"] == {"batch": 0, "lane": 0}
    assert ends["8"]["args"] == {"batch": 0, "lane": 1}
    # inner nests inside outer on the same thread track
    (_, tid_out, s_out, e_out, a_out), = tr.spans("outer")
    (_, tid_in, s_in, e_in, _), = tr.spans("inner")
    assert a_out == {"k": 1}
    assert tid_out == tid_in and s_out <= s_in and e_in <= e_out


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    tr.counter("c", v=1)
    tr.instant("i")
    tr.complete("c", 0, 1)
    tr.requests([1], [0], 1, 2)
    assert tr.events() == []
    # the shared null tracer is the same object for every span
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")


def test_tracer_ring_buffer_bounded():
    tr = Tracer(cap=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    tr.requests([1, 2], [0, 0], 1, 2, batch=0)  # a batch record is one event
    assert len(tr.events()) == 4
    assert tr.dropped == 7
    assert tr.export()["otherData"]["dropped_events"] == 7
    assert sorted(r[0] for r in tr.spans()) == [
        "request", "request", "s7", "s8", "s9"]


def test_tracer_spans_land_on_the_profiler_clock(tmp_path):
    """A span mapped by the trace's ``profile_start_time`` lies on the
    ``TraceAnnotation`` around the same sleep, within 50 us at both ends."""
    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"probe{i}"):
            with tr.span(f"probe{i}"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    start = next(dict(p.stats)["profile_start_time"] for p in data.planes
                 if p.name == "Task Environment")
    marks = {e.name: (e.start_ns, e.end_ns) for p in data.planes
             for ln in p.lines for e in ln.events if e.name.startswith("probe")}
    for name, _, t0, t1, _ in tr.spans():
        a0, a1 = marks[name]
        assert abs(t0 - start - a0) < 50_000 and abs(t1 - start - a1) < 50_000


def test_validate_rejects_malformed_traces():
    ok = {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0, "dur": 5}
    with pytest.raises(AssertionError):
        validate_chrome_trace([ok])  # not object form
    with pytest.raises(AssertionError, match="missing 'tid'"):
        validate_chrome_trace({"traceEvents": [
            {k: v for k, v in ok.items() if k != "tid"}]})
    with pytest.raises(AssertionError, match="partially overlaps"):
        validate_chrome_trace({"traceEvents": [
            ok, {**ok, "name": "b", "ts": 3, "dur": 5}]})
    with pytest.raises(AssertionError, match="never ended"):
        validate_chrome_trace({"traceEvents": [
            {"ph": "b", "cat": "r", "name": "r", "id": "1",
             "pid": 1, "tid": 1, "ts": 0}]})
    # properly nested + disjoint passes
    validate_chrome_trace({"traceEvents": [
        ok, {**ok, "name": "in", "ts": 1, "dur": 2},
        {**ok, "name": "next", "ts": 6, "dur": 1}]})


def test_tracer_thread_safety_smoke():
    tr = Tracer(cap=10000)

    def worker(k):
        for i in range(200):
            with tr.span(f"w{k}"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events()) == 800
    validate_chrome_trace(tr.export())


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_instruments():
    m = MetricsRegistry("t")
    m.inc("a")
    m.inc("a", 2)
    m.set_gauge("g", 5)
    m.set_gauge("g", 2)
    for v in (1.0, 2.0, 3.0):
        m.observe("h", v)
    snap = m.snapshot()
    assert snap["a"] == {"kind": "counter", "value": 3}
    assert snap["g"]["value"] == 2 and snap["g"]["min"] == 2 and snap["g"]["max"] == 5
    assert snap["h"]["count"] == 3 and snap["h"]["sum"] == 6.0
    with pytest.raises(TypeError):
        m.gauge("a")  # kind mismatch


def test_metrics_histogram_percentile_edges():
    m = MetricsRegistry()
    h = m.histogram("h")
    assert h.percentile(50) == 0.0  # empty: documented sentinel
    h.observe(7.0)
    for pct in (50, 95, 99):
        assert h.percentile(pct) == 7.0  # single sample


def test_metrics_dump(tmp_path):
    m = MetricsRegistry()
    m.inc("x")
    path = m.dump(tmp_path / "m.json")
    assert json.loads(path.read_text())["x"]["value"] == 1


def test_executor_cache_metrics():
    m = MetricsRegistry()
    cache = BucketedExecutorCache(
        lambda b: (lambda *a: b), (1, 4), prewarm=True, metrics=m)
    assert m.value("executor_cache.lowerings") == 2
    cache.for_batch(3)
    cache.for_batch(1)
    assert m.value("executor_cache.hits") == 2
    assert m.snapshot()["executor_cache.lower_s"]["count"] == 2


def test_cache_fifo_named_metrics():
    from repro.core.segments import cache_fifo

    cache = {}
    name = "test_fifo_metrics"
    before_evict = REGISTRY.value(f"cache.{name}.evictions") or 0
    cache_fifo(cache, "k1", 1, lambda: 1, name=name)
    cache_fifo(cache, "k1", 1, lambda: 1, name=name)  # hit
    cache_fifo(cache, "k2", 1, lambda: 2, name=name)  # evicts k1
    assert REGISTRY.value(f"cache.{name}.builds") == 2
    assert REGISTRY.value(f"cache.{name}.hits") == 1
    assert REGISTRY.value(f"cache.{name}.evictions") == before_evict + 1


# ---------------------------------------------------------------------------
# ServeStats: race fix + percentile window contract
# ---------------------------------------------------------------------------


def test_servestats_latency_ms_empty_window():
    s = ServeStats()
    for pct in (50, 95, 99):
        assert s.latency_ms(pct) == 0.0  # documented empty-window sentinel


def test_servestats_latency_ms_single_sample():
    s = ServeStats(latencies_s=[0.004])
    for pct in (50, 95, 99):
        assert s.latency_ms(pct) == pytest.approx(4.0)


def test_servestats_snapshot_is_isolated_copy():
    s = ServeStats()
    bid0 = s.record_batch(bucket=4, n=3)
    s.record_latencies([0.001, 0.002, 0.003])
    snap = s.snapshot()
    s.record_batch(bucket=4, n=4)
    s.record_latencies([0.009])
    assert bid0 == 0
    assert snap.batches == 1 and snap.requests == 3
    assert snap.latencies_s == [0.001, 0.002, 0.003]
    assert snap.padded_lanes == 1
    assert s.batches == 2 and s.latency_count() == 4
    # dataclasses.replace must not share the lock either (init=False field)
    assert snap._lock is not s._lock


def test_servestats_concurrent_append_consistent():
    # The writer is bounded (not the reader): an unbounded spin-appender
    # makes every snapshot copy O(n) on a list that grows without limit.
    s = ServeStats()
    done = threading.Event()

    def appender():
        for _ in range(20_000):
            s.record_latencies([0.001])
        done.set()

    t = threading.Thread(target=appender)
    t.start()
    try:
        while not done.is_set():
            snap = s.snapshot()
            # a torn read would raise or return a list mid-mutation;
            # the locked snapshot is always internally consistent
            assert len(snap.latencies_s) == len(list(snap.latencies_s))
            s.latency_ms(99)
    finally:
        t.join()
    assert s.latency_count() == 20_000
    assert s.latency_ms(99) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Engine tracing: pipeline overlap + zero spans when disabled
# ---------------------------------------------------------------------------


def test_engine_disabled_tracer_records_nothing(lenet_engine_parts):
    graph, plan, params = lenet_engine_parts
    tr = Tracer(enabled=False)
    eng = CNNEngine.from_graph(graph, plan, params, buckets=(4,),
                               policy=CoalescePolicy(max_batch=4), tracer=tr)
    xs = np.random.default_rng(0).standard_normal((8, 1, 32, 32)).astype(np.float32)
    with eng:
        _, run = eng.serve(xs)
    assert run.requests == 8
    assert tr.events() == []


def _nested(inner, outer):
    """Spans of ``inner`` each lie inside the ``outer`` span of the same
    batch on the same thread."""
    by_batch = {a["batch"]: (tid, s, e) for _, tid, s, e, a in outer}
    for _, tid, s, e, a in inner:
        o_tid, o_s, o_e = by_batch[a["batch"]]
        if tid != o_tid or s < o_s or e > o_e:
            return False
    return bool(inner)


def test_engine_burst_stage_overlaps_device(lenet_engine_parts):
    """The serving-PR design claim: with the depth-1 inflight queue, the
    dispatcher stages batch k+1 while the completer still blocks on the
    device value of batch k — visible as overlapping stage/device spans on
    the two thread tracks."""
    graph, plan, params = lenet_engine_parts
    tr = Tracer()
    eng = CNNEngine.from_graph(graph, plan, params, buckets=(8,),
                               policy=CoalescePolicy(max_batch=8), tracer=tr)
    xs = np.random.default_rng(1).standard_normal((64, 1, 32, 32)).astype(np.float32)
    with eng:
        _, run = eng.serve(xs)  # all at once: a saturating burst
    assert run.requests == 64 and run.batches >= 8
    validate_chrome_trace(tr.export())

    def batch_arg(ev):
        return ev.get("args", {}).get("batch")

    devices = [(t, t + d, batch_arg(e)) for t, d, e in
               ((s["ts"], s["dur"], s) for s in _x_events(tr, "device"))]
    stages = [(t, t + d, batch_arg(e)) for t, d, e in
              ((s["ts"], s["dur"], s) for s in _x_events(tr, "stage"))]
    overlaps = [
        (bs, bd)
        for s0, s1, bs in stages
        for d0, d1, bd in devices
        if bs > bd and s0 < d1 and d0 < s1
    ]
    # ~7 opportunities in 8+ batches; the pipeline only fails to overlap if
    # double buffering is broken
    assert overlaps, "no stage(k+1)/device(k) overlap found in a burst"
    # request lifecycle spans carry batch/bucket/lane args
    ends = [e for e in tr.export()["traceEvents"]
            if e["ph"] == "e" and e["name"] == "request"]
    assert len(ends) == 64
    assert all(
        {"batch", "bucket", "lane"} <= set(e["args"]) for e in ends)
    # the per-batch records count every request once, queued before closed
    reqs = tr.spans("request")
    assert sorted(a["rid"] for *_, a in reqs) == list(range(64))
    assert all(s <= a["closed_ns"] <= e for _, _, s, e, a in reqs)
    # H2D and launch nest in dispatch; the handoff to the completer shows
    dispatch = tr.spans("dispatch")
    assert _nested(tr.spans("h2d"), dispatch)
    assert _nested(tr.spans("launch"), dispatch)
    assert len(tr.spans("handoff")) == run.batches
    # submit() records nothing: every event is on an engine thread
    assert threading.get_ident() not in {ev[2] for ev in tr.events()}


def _x_events(tr, name):
    return [e for e in tr.export()["traceEvents"]
            if e["ph"] == "X" and e["name"] == name]


def test_engine_coalesce_starts_at_first_request(lenet_engine_parts):
    """After the queue stood empty, ``coalesce`` spans the policy's wait
    for more requests, not the idle time before the first one."""
    graph, plan, params = lenet_engine_parts
    tr = Tracer()
    policy = CoalescePolicy(max_batch=8, max_wait_s=0.002)
    eng = CNNEngine.from_graph(graph, plan, params, buckets=(8,),
                               policy=policy, tracer=tr)
    x = np.zeros((1, 32, 32), np.float32)
    with eng:
        eng.submit(x).result(timeout=60)
        time.sleep(0.05)
        eng.submit(x).result(timeout=60)
    _, _, t0, t1, _ = tr.spans("coalesce")[-1]
    assert (t1 - t0) * 1e-9 <= policy.max_wait_s + 0.005


def test_engine_metrics_wired(lenet_engine_parts):
    graph, plan, params = lenet_engine_parts
    eng = CNNEngine.from_graph(graph, plan, params, buckets=(1, 4),
                               policy=CoalescePolicy(max_batch=4))
    xs = np.random.default_rng(2).standard_normal((8, 1, 32, 32)).astype(np.float32)
    with eng:
        _, run = eng.serve(xs)
    snap = eng.metrics.snapshot()
    assert snap["executor_cache.lowerings"]["value"] == 2  # both buckets AOT
    assert snap["engine.prewarm_s"]["value"] == pytest.approx(
        run.prewarm_s)
    # batch and latency accounting lives in ServeStats alone
    stats = eng.stats.snapshot()
    assert stats.batches == run.batches >= 2
    assert len(stats.latencies_s) == stats.requests == 8
    assert sum(stats.bucket_hist.values()) == stats.batches
    assert stats.padded_lanes == sum(
        b * k for b, k in stats.bucket_hist.items()) - 8
    assert not any(k.startswith("engine.") and k != "engine.prewarm_s"
                   for k in snap)


# ---------------------------------------------------------------------------
# StreamServer spans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ds_cnn_parts():
    import jax

    from repro.core import nn
    from repro.core.graph import ds_cnn

    g = ds_cnn()
    return g, nn.init_params(g.to_sequential(), jax.random.PRNGKey(0))


def test_stream_server_push_spans(ds_cnn_parts):
    from repro.serve.cnn_engine import StreamServer

    g, params = ds_cnn_parts
    tr = Tracer()
    srv = StreamServer.from_graph(g, params, prewarm=False, tracer=tr)
    frame = np.zeros((1, 10), np.float32)
    outs = [srv.push("a", frame) for _ in range(4)]  # emits every 2nd frame
    validate_chrome_trace(tr.export())
    pushes = tr.spans("push")
    assert len(pushes) == 4 and all(a == {"stream": "a"} for *_, a in pushes)

    def inside(name):
        """For each push, how many ``name`` spans it holds."""
        kids = tr.spans(name)
        return [sum(1 for _, kt, ks, ke, _ in kids
                    if kt == tid and s <= ks and ke <= e)
                for _, tid, s, e, _ in pushes]

    for name in ("h2d", "step"):
        assert inside(name) == [1, 1, 1, 1], name
    assert inside("d2h") == [int(o is not None) for o in outs] == [0, 1, 0, 1]
    assert inside("sync") == [0, 0, 0, 0]  # the host never waits on `emitted`

    off = Tracer(enabled=False)
    srv = StreamServer.from_graph(g, params, prewarm=False, tracer=off)
    srv.push("b", frame)
    assert off.events() == []


def test_dag_executor_names_every_segment():
    """The lowered ds_cnn_kws() int8 executor carries one named scope per
    compiled segment, ``seg<index>.<form>``."""
    import jax
    import jax.numpy as jnp

    from repro.core import fusion, nn, quantize, schedule, segments
    from repro.core.graph import ds_cnn_kws
    from repro.quant.exec import make_int8_executor

    g = ds_cnn_kws()
    fused = fusion.fuse_dag(g)
    params = fusion.rename_params(
        fused, nn.init_params(g, jax.random.PRNGKey(0)))
    calib = jax.random.normal(jax.random.PRNGKey(1), (8, 1, 49, 10))
    qm = quantize.quantize_dag(fused, params, calib)
    plan = schedule.plan_dag(g, io_dtype_bytes=1)
    fn, qparams = make_int8_executor(qm, plan)
    text = fn.lower(qparams, jax.ShapeDtypeStruct((4, 1, 49, 10), jnp.int8)
                    ).as_text(debug_info=True)
    _, _, segs = segments.segments_for_plan(qm.graph, plan)
    assert len(segs) >= 2
    for i, seg in enumerate(segs):
        assert f"seg{i}.{seg.form}/" in text, (i, seg.form)


# ---------------------------------------------------------------------------
# Static cost model + arena timeline invariants
# ---------------------------------------------------------------------------


def test_ds_cnn_macs_match_hand_computation():
    """Layer-for-layer derivation of Zhang et al.'s DS-CNN cost:
    conv1 Conv2d(1→64, k5, s2, p2) on (1,49,10) → (64,25,5);
    4 × [dw k3 p1 + pw 1×1] on (64,25,5); fc Linear(320→12)."""
    conv1 = 64 * 25 * 5 * 1 * 5 * 5            # 200_000
    dw = 64 * 25 * 5 * 3 * 3                   # 72_000 each
    pw = 64 * 25 * 5 * 64 * 1 * 1              # 512_000 each
    fc = 320 * 12                              # 3_840
    hand_total = conv1 + 4 * (dw + pw) + fc
    assert hand_total == 2_539_840

    for int8 in (False, True):
        b = report.build_workload("ds_cnn", int8=int8)
        seg = report.segment_report(b["graph"], b["plan"])
        assert seg["total_macs"] == hand_total
        # per-segment static costs must sum to the total (the CI assert)
        assert sum(s["macs"] for s in seg["segments"]) == hand_total


def test_macs_invariant_under_fusion_and_views():
    from repro.core.graph import Conv2d, FusedConvPool, MaxPool2d

    conv = Conv2d(in_channels=1, out_channels=6, kernel_size=5)
    fused = FusedConvPool(conv=conv, pool_kernel=2, pool_stride=2)
    in_shape = (1, 32, 32)
    assert fused.macs(in_shape) == conv.macs(in_shape) == 6 * 28 * 28 * 25
    assert MaxPool2d().macs((6, 28, 28)) == 0  # data movement costs 0 MACs


@pytest.mark.parametrize("name", report.WORKLOADS)
@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_arena_timeline_peak_equals_planner_bytes(name, int8):
    b = report.build_workload(name, int8=int8)
    tl = report.arena_timeline(b["plan"])
    assert tl["peak_bytes"] == tl["arena_bytes"] == b["plan"].arena_bytes
    # every schedule position is covered and the peak position is real
    assert len(tl["positions"]) == len(b["plan"].buffers)
    assert tl["positions"][tl["peak_pos"]]["top_bytes"] == tl["peak_bytes"]


def test_known_planner_arena_bytes():
    expect = {
        ("lenet", False): 8800, ("lenet", True): 2200,
        ("residual_cifar", False): 32768, ("residual_cifar", True): 8192,
        ("ds_cnn", False): 64000, ("ds_cnn", True): 16000,
    }
    for (name, int8), bytes_ in expect.items():
        b = report.build_workload(name, int8=int8)
        assert b["plan"].arena_bytes == bytes_, (name, int8)


def test_ascii_memory_map_renders(lenet_bundle):
    txt = report.ascii_memory_map(lenet_bundle["plan"], width=40)
    lines = txt.splitlines()
    # one row per schedule position + header (2) + legend
    assert len(lines) == len(lenet_bundle["plan"].buffers) + 3
    assert "legend:" in lines[-1]


def test_segment_report_kinds_ds_cnn():
    b = report.build_workload("ds_cnn", int8=True)
    seg = report.segment_report(b["graph"], b["plan"])
    # the dw/pw backbone compiles into one period-2 scan (the PR 6 win)
    assert seg["segments_by_kind"].get("periodic-scan", 0) >= 1
    periodic = next(s for s in seg["segments"]
                    if s["kind"] == "periodic-scan")
    assert periodic["period"] == 2


def test_timed_segments_smoke(lenet_bundle):
    t = report.timed_segments(lenet_bundle, iters=1)
    rows = t["by_time"]
    assert len(rows) == report.segment_report(
        lenet_bundle["graph"], lenet_bundle["plan"])["n_segments"]
    assert all(r["measured_s"] > 0 for r in rows)
    assert sum(r["model_frac"] for r in rows) == pytest.approx(1.0, abs=0.01)
    # discrepancy = measured share − model share, so it sums to ~0
    assert sum(r["discrepancy"] for r in rows) == pytest.approx(0.0, abs=0.02)


def test_obs_report_cli_smoke(tmp_path):
    import sys
    sys.path.insert(0, "scripts")
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    obs_report.main(["lenet", "--int8", "--no-trace",
                     "--out", str(tmp_path)])
    combined = json.loads((tmp_path / "obs_report.json").read_text())
    assert combined["lenet.int8"]["arena_bytes"] == 2200
    seg = json.loads((tmp_path / "lenet.int8.segments.json").read_text())
    assert seg["total_macs"] == combined["lenet.int8"]["total_macs"]
    assert (tmp_path / "lenet.int8.arena.txt").exists()
