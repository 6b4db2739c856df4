"""End-to-end serving-trace export demo (ISSUE 7).

Stands up the continuous-batching CNN engine with an *enabled*
:class:`repro.obs.trace.Tracer`, drives one burst of traffic, and writes a
Chrome trace-event JSON you can open directly in Perfetto:

  1. ``PYTHONPATH=src python examples/trace_serving.py``
  2. open https://ui.perfetto.dev and drag ``serving_trace.json`` in
     (or chrome://tracing on older Chrome).

What to look at in the UI (DESIGN.md §11):

* the ``cnn-engine-dispatch`` track: ``coalesce → stage → dispatch
  (h2d, launch) → handoff`` spans per batch — the host side of the
  pipeline; ``handoff`` is where the dispatcher waits on the completer;
* the ``cnn-engine-complete`` track: ``device`` (blocking on the device
  value) and ``complete`` (output scatter) spans — watch ``stage`` of
  batch *k+1* sit on top of ``device`` of batch *k*: that overlap *is* the
  double-buffered pipeline;
* the async ``request`` track: one span per request id from submit to
  completion, with batch id / bucket / lane stamped in the end-event args
  (recorded once per batch by the completer, not by ``submit``);
* the ``queue_depth`` / ``batch_occupancy`` counter tracks.

Also dumps the engine's metrics registry (executor-cache hits/lowerings,
prewarm time) as ``serving_metrics.json``; batch and latency accounting
is in the run's ``ServeStats``.

    PYTHONPATH=src python examples/trace_serving.py [--requests N] [--out DIR]
"""
import argparse
import sys

sys.path.insert(0, "src")

from pathlib import Path

import jax
import numpy as np

from repro.core import fusion, nn, schedule
from repro.core.graph import lenet5, DAGGraph
from repro.obs.trace import Tracer, validate_chrome_trace
from repro.serve.cnn_engine import CNNEngine, CoalescePolicy


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--out", default=".")
    args = ap.parse_args()

    g = DAGGraph.from_sequential(lenet5())
    fused = fusion.fuse_dag(g)
    plan = schedule.plan_dag(g)
    params = fusion.rename_params(fused, nn.init_params(g, jax.random.PRNGKey(0)))

    tracer = Tracer(process_name="lenet.f32 serving")
    engine = CNNEngine.from_graph(
        fused, plan, params,
        buckets=(1, 4, 8), policy=CoalescePolicy(max_batch=8, max_wait_s=0.002),
        tracer=tracer,
    )

    rng = np.random.default_rng(0)
    images = rng.standard_normal((args.requests, 1, 32, 32)).astype(np.float32)
    arrivals = [(i // 8) * 0.001 for i in range(args.requests)]  # burst-8
    with engine:
        reqs, run = engine.serve(images, arrivals)

    trace = tracer.export()
    validate_chrome_trace(trace)  # schema-checked before anyone loads it
    out = Path(args.out)
    trace_path = tracer.dump(out / "serving_trace.json")
    metrics_path = engine.metrics.dump(out / "serving_metrics.json")

    devices = tracer.spans("device")
    stages = tracer.spans("stage")
    overlaps = sum(
        1 for (_, _, d0, d1, _) in devices for (_, _, s0, s1, _) in stages
        if s0 < d1 and d0 < s1
    )
    print(f"served {run.requests} requests in {run.batches} batches "
          f"({run.qps:.0f} qps, p99 {run.latency_ms(99):.2f} ms)")
    print(f"trace: {trace_path} ({len(trace['traceEvents'])} events, "
          f"{len(devices)} device spans, {overlaps} stage/device overlaps)")
    print(f"metrics: {metrics_path}")
    print("open https://ui.perfetto.dev and drag the trace file in")


if __name__ == "__main__":
    main()
