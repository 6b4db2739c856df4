"""One traced run of a serve or streams cell, with the program's own spans.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s> \
        [--tracer 0|1] [--host-tracer 0|1] [--out <file.json>]

Runs the cell as ``run.py --trace 1`` does, and besides: after set-up it
hands an enabled ``repro.obs.trace.Tracer``, sized for the whole window, to
the cell's ``CNNEngine`` or ``StreamServer`` (``--tracer 0`` leaves the
default disabled one, to price the tracer); it stamps the tracer's clock
around the window, so that the window is found without the profiler's host
tracer (``--host-tracer 0``); and before the window it wraps a few short
sleeps both in a ``TraceAnnotation`` and in a tracer span.

Prints the window line, then one JSON result: ``correct``, the cell's
per-layer metrics and the ones read from the program's spans (readers in
``metrics/``), ``program_gaps`` and ``work_idle_s`` (``program_spans``), the
tracer's ``dropped`` and event count, the offsets between mapped spans and
the annotations around the same work, per span name its count, median ms
and total s in the window (``span_ms``), and the host cost of one tracer
event. ``--out`` writes the result there too.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gc
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import run  # noqa: I001  (puts src/ on the path, as run.py does)

PROGRAM_METRICS = {
    "requests": ("engine.queue_wait_ms", "engine.host_ms_per_batch",
                 "device_idle.engine_work.serve"),
    "streams": ("stream.step_ms", "stream.sync_ms"),
}
# Ring size per second of window: serve records about 11 events a batch at
# some 300 batches/s, streams about 4.5 a frame at 350 frames/s.
EVENTS_PER_S = 20_000
PROBES = 5


def event_cost_ns(n: int = 200_000) -> dict:
    """Host ns per ``with tracer.span("x", batch=1)`` on an enabled and on
    the disabled tracer, and per turn of the empty loop around them."""
    from repro.obs.trace import NULL_TRACER, Tracer

    out = {}
    t0 = time.perf_counter_ns()
    for _ in range(n):
        pass
    out["loop"] = (time.perf_counter_ns() - t0) / n
    for label, tr in (("span_on", Tracer(cap=n)), ("span_off", NULL_TRACER)):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tr.span("x", batch=1):
                pass
        out[label] = (time.perf_counter_ns() - t0) / n
    return out


def offsets(marks, spans, start_ns: int) -> dict:
    """How far each mapped span lies inside the annotation that holds it:
    ``start`` = span start - annotation start, ``end`` = annotation end -
    span end, in us (median and largest magnitude)."""
    marks = sorted(marks)
    starts = [s for s, _ in marks]
    d0, d1 = [], []
    for _, _, s, e, _ in spans:
        s, e = s - start_ns, e - start_ns
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and marks[i][1] >= s:
            d0.append((s - marks[i][0]) * 1e-3)
            d1.append((marks[i][1] - e) * 1e-3)
    if not d0:
        return {"n": 0}
    return {"n": len(d0), "n_spans": len(spans),
            "start_us": [statistics.median(d0), max(d0, key=abs)],
            "end_us": [statistics.median(d1), max(d1, key=abs)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--host-tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    import program_spans
    import trace_reduce
    from repro.obs.trace import Tracer

    bench = run.spec()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    if jax.devices()[0].platform != "tpu":
        print("program_trace: no TPU found", file=sys.stderr)
        return 2
    run.use_compile_cache()
    cfg, traffic, weights, drv = run.prepare(bench, cell, args.seed)
    kind = traffic["kind"]
    if kind not in PROGRAM_METRICS:
        print(f"program_trace: the {kind} mix runs no engine", file=sys.stderr)
        return 2
    jax.block_until_ready(weights)
    drv.setup()
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - run.T_START
    cost = event_cost_ns()

    tracer = Tracer(cap=int(args.seconds * EVENTS_PER_S)) if args.tracer else None
    if tracer:
        setattr(drv.engine if kind == "requests" else drv.srv, "tracer", tracer)
    probe = Tracer()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = args.host_tracer
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(PROBES):
        with jax.profiler.TraceAnnotation("bench.probe"):
            with probe.span("probe", i=i):
                time.sleep(0.001)
    t0 = time.time_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        rec = drv.window(args.seconds)
    t1 = time.time_ns()
    jax.profiler.stop_trace()
    path = str(next(Path(trace_dir).rglob("*.xplane.pb")))
    start = program_spans.profile_start_ns(path)
    ev = trace_reduce.events(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not args.host_tracer:  # no annotations: the window by the tracer's clock
        ev["host"] = [(trace_reduce.WINDOW, t0 - start, t1 - start)]
    rec.update(setup_s=setup_s, cfg=cfg, trace=trace_reduce.reduce(ev, cell["chips"]))

    def marks(name):
        return [(s, e) for n, s, e in ev["host"] if n == name]

    out = {"tracer": args.tracer, "host_tracer": args.host_tracer,
           "event_cost_ns": cost,
           "probe_offsets": offsets(marks("bench.probe"), probe.spans("probe"), start)}
    if tracer:
        rec.update(program_spans.record(tracer, t0, t1))
        rec["program_gaps"] = program_spans.program_gaps(ev, rec["spans"], start)
        rec["work_idle_s"] = program_spans.work_idle_s(ev, rec["spans"], start)
        by_name = collections.defaultdict(list)
        for name, _, s, e, _ in rec["spans"]:
            by_name[name].append(e - s)
        out.update(dropped=tracer.dropped, events=len(tracer.events()),
                   spans_in_window=len(rec["spans"]), program_gaps=rec["program_gaps"],
                   work_idle_s=rec["work_idle_s"],
                   span_ms={n: [len(d), statistics.median(d) * 1e-6, sum(d) * 1e-9]
                            for n, d in sorted(by_name.items())})
        if kind == "streams":
            out["push_offsets"] = offsets(marks("bench.push"),
                                          [s for s in rec["spans"] if s[0] == "push"], start)
    _, per_layer = run.cell_metrics(bench, args.workload)
    names = [m["name"] for m in per_layer] + list(PROGRAM_METRICS[kind])
    out["metrics"] = {n: run.reader(n)(rec) for n in names}
    out["idle_gaps"] = rec["trace"]["idle_gaps"]
    out["idle_s"] = rec["trace"]["window_s"] - rec["trace"]["busy_s"]

    x, y, missing = drv.sample(run.CHECK_ROWS)
    drv.close()
    gap = run.logit_gap(cfg, weights, x, y)
    out["correct"] = gap == run.LOGIT_GAP_LIMIT and missing == 0 and len(x) > 0
    window = {k: v for k, v in rec.items()
              if isinstance(v, (int, float)) and not isinstance(v, bool)}
    print(json.dumps({"window": window}), flush=True)
    print(json.dumps(out), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"window": window, **out}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
