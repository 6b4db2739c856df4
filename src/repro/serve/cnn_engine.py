"""Continuous-batching CNN serving engine over the compiled arena executors.

What a deployed KWS/vision endpoint faces is not the per-inference setting
CMSIS-NN benchmarks but variable-arrival single-image traffic; throughput
there comes from dynamic batching and from keeping the compiled executors
and their donated arenas resident across steps.  This engine is the
serving-side realization of the paper's static-arena plan:

* **Bucketed executor ladder** — one arena executor per batch size on a
  small ladder (1/2/4/8/16 by default), each ``.lower().compile()``'d
  ahead of time at engine construction (``pingpong.aot_compile``), held in
  the :class:`repro.serve.step.BucketedExecutorCache` shared with the LLM
  engine.  No request ever pays first-call jit cost; batches pad up to the
  nearest bucket with zero images whose outputs are dropped.

* **Ping-pong staging banks** — each bucket owns a pair of host staging
  arrays allocated once and alternated between consecutive dispatches, the
  paper's two-bank discipline at serving granularity: while the device
  still reads the H2D copy of batch *k*, the host stacks batch *k+1* into
  the other bank.  (Inside each compiled executor the scan carry is donated
  by XLA exactly as in per-call use.)

* **Async host pipeline** — a dispatcher thread drains the request queue,
  stacks and dispatches (JAX dispatch is asynchronous), and hands the
  in-flight device value to a completer thread that blocks, scatters
  outputs and stamps completion times.  The handoff queue holds at most one
  in-flight batch (double buffering), so coalescing + H2D of batch *k+1*
  overlaps device compute of batch *k* and memory stays bounded.

* **Coalescing policy** — the dispatcher takes the first queued request,
  then keeps draining until ``max_batch`` requests are in hand or
  ``max_wait_s`` has elapsed since the first one: the knob that trades p50
  latency (shorter wait) against throughput (fuller buckets).

* **Data-parallel mesh scale-out** — pass ``mesh=`` (to the constructors)
  to shard every bucket batch over a ``('data',)`` device mesh
  (DESIGN.md §12): weights replicate, the bucket's batch axis maps to
  ``NamedSharding(mesh, P('data'))``, and each device runs the full
  two-bank arena over its batch shard.  Buckets round **up** to mesh-size
  multiples (1/2/4/8/16 on 4 devices → 4/8/16) so every compiled
  executable shards evenly — the extra lanes are ordinary padding lanes,
  already proven row-independent, so engine outputs stay bit-exact against
  the single-device engine.

Numerics are whatever the wrapped executor computes: engine outputs are
bit-exact against the same executor called directly at the same bucket —
padding rows never contaminate real rows — and therefore inherit the
executors' guarantees (int8: bit-exact vs ``simulate_int8_dag_forward``;
float: bit-exact vs the jitted batched oracle, see ``tests/test_serving``).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pingpong
from repro.core.graph import DAGGraph
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.step import BucketedExecutorCache, enable_persistent_cache

DEFAULT_BUCKETS = (1, 2, 4, 8, 16)


def _input_shape(graph) -> Tuple[int, ...]:
    """Per-image input shape of either graph kind (the Input pseudo-layer)."""
    if isinstance(graph, DAGGraph):
        return tuple(graph.nodes[0].layer.shape)
    return tuple(graph.layers[0].shape)


@dataclasses.dataclass(frozen=True)
class CoalescePolicy:
    """When the dispatcher closes a batch.

    ``max_batch`` caps the drain (at most the largest bucket);
    ``max_wait_s`` is the deadline measured from the first request taken for
    the batch — raising it fills buckets better under sparse arrivals at the
    cost of p50 latency.
    """

    max_batch: int = 16
    max_wait_s: float = 0.002


@dataclasses.dataclass
class CNNRequest:
    """One single-image inference request."""

    rid: int
    x: np.ndarray
    t_submit: float = 0.0
    t_done: float = 0.0
    y: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False
    )

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """The output row; re-raises the engine error that failed this
        request instead."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} not completed")
        if self.error is not None:
            raise self.error
        return self.y

    def _fail(self, error: BaseException) -> None:
        self.error = error
        self.t_done = time.perf_counter()
        self._done.set()


@dataclasses.dataclass
class ServeStats:
    """Engine-side accounting for one serving run.

    The engine's dispatcher and completer threads both mutate an instance
    concurrently, so every mutation and every multi-field read goes through
    ``_lock`` (``record_batch`` / ``record_latencies`` / ``snapshot``).
    Instances returned by :meth:`snapshot` (and the per-run stats from
    ``CNNEngine.serve``) are plain frozen-in-time copies — safe to read
    field-by-field without the lock.
    """

    requests: int = 0
    batches: int = 0
    padded_lanes: int = 0
    bucket_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0
    prewarm_s: float = 0.0
    compiles: int = 0
    # init=False: dataclasses.replace / snapshot give the copy its own lock.
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, init=False, repr=False, compare=False
    )

    @property
    def qps(self) -> float:
        return self.requests / self.wall_s if self.wall_s else 0.0

    @property
    def avg_batch(self) -> float:
        return self.requests / self.batches if self.batches else 0.0

    @property
    def padding_frac(self) -> float:
        lanes = self.requests + self.padded_lanes
        return self.padded_lanes / lanes if lanes else 0.0

    def record_batch(self, bucket: int, n: int) -> int:
        """Account one dispatched batch; returns its batch id (0-based,
        engine-lifetime ordinal)."""
        with self._lock:
            bid = self.batches
            self.batches += 1
            self.requests += n
            self.padded_lanes += bucket - n
            self.bucket_hist[bucket] = self.bucket_hist.get(bucket, 0) + 1
            return bid

    def record_latencies(self, latencies_s) -> None:
        with self._lock:
            self.latencies_s.extend(latencies_s)

    def latency_count(self) -> int:
        with self._lock:
            return len(self.latencies_s)

    def snapshot(self) -> "ServeStats":
        """A consistent point-in-time copy (mutable fields deep-copied, so
        the copy is immune to further engine-thread appends)."""
        with self._lock:
            return dataclasses.replace(
                self,
                bucket_hist=dict(self.bucket_hist),
                latencies_s=list(self.latencies_s),
            )

    def latency_ms(self, pct: float) -> float:
        """The ``pct`` latency percentile in milliseconds.

        Contract for the window edge cases (unit-tested): an **empty
        window** (no completed requests) returns ``0.0`` for every
        percentile — a sentinel, not a measurement (callers that must
        distinguish check ``latencies_s``); a **single-sample** window
        returns that sample for every percentile (``np.percentile`` on one
        value).
        """
        with self._lock:
            xs = list(self.latencies_s)
        if not xs:
            return 0.0
        return float(np.percentile(np.asarray(xs), pct) * 1e3)

    def summary(self) -> Dict[str, float]:
        return {
            "requests": self.requests,
            "batches": self.batches,
            "avg_batch": round(self.avg_batch, 2),
            "padding_frac": round(self.padding_frac, 4),
            "qps": round(self.qps, 1),
            "p50_ms": round(self.latency_ms(50), 3),
            "p95_ms": round(self.latency_ms(95), 3),
            "p99_ms": round(self.latency_ms(99), 3),
        }


class CNNEngine:
    """Continuous-batching engine over one compiled arena executor.

    ``executor_fn`` is a jitted ``(params, x) -> y`` executor from
    ``pingpong.make_scan_executor`` / ``make_dag_executor`` (float or int8 —
    the numerics travel in the executor and ``params``).  The engine AOT
    compiles it once per bucket at construction (``prewarm=True``; pass
    ``False`` to measure the cold-start cost the ladder removes), then
    serves ``submit``'d requests from two pipelined worker threads.

    A worker that raises (a failed dispatch, or a device error surfacing
    when the completer reads the output) fails its batch and every queued
    request with that error, which ``CNNRequest.result`` re-raises; the
    engine then refuses new work, and :meth:`stop` still returns.

    Use as a context manager, or call :meth:`start` / :meth:`stop`.
    """

    def __init__(
        self,
        executor_fn: Callable,
        params,
        in_shape: Sequence[int],
        dtype,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        policy: Optional[CoalescePolicy] = None,
        prewarm: bool = True,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        data_parallel=None,
        persistent_cache: bool = False,
    ):
        # Enable the disk compilation cache *before* the ladder lowers, so
        # a fresh replica's prewarm deserializes instead of recompiling.
        if persistent_cache:
            enable_persistent_cache()
        self.in_shape = tuple(int(d) for d in in_shape)
        self.dtype = jnp.dtype(dtype)
        self.policy = policy or CoalescePolicy()
        # Read per event by the worker loops, so a caller may swap in an
        # enabled Tracer on a running engine; defaults to the shared no-op.
        self.tracer = tracer or NULL_TRACER
        self.metrics = metrics or MetricsRegistry("cnn_engine")
        # Mesh scale-out (DESIGN.md §12): ``executor_fn`` must have been
        # built with the same policy (the constructors do); weights are
        # placed replicated once, buckets round up to mesh-size multiples
        # so every compiled batch shards evenly.
        self.data_parallel = data_parallel
        if data_parallel is not None:
            params = data_parallel.replicate(params)
            buckets = tuple(data_parallel.padded_batch(b) for b in buckets)
        self.params = params
        buckets = tuple(sorted({int(b) for b in buckets}))
        if self.policy.max_batch > buckets[-1]:
            # the drain can never exceed the largest compiled bucket
            self.policy = dataclasses.replace(
                self.policy, max_batch=buckets[-1]
            )
        t0 = time.perf_counter()
        self._cache = BucketedExecutorCache(
            lambda b: pingpong.aot_compile(
                executor_fn, params, (b, *self.in_shape), self.dtype
            ),
            buckets,
            prewarm=prewarm,
            metrics=self.metrics,
        )
        self.stats = ServeStats(
            prewarm_s=time.perf_counter() - t0 if prewarm else 0.0
        )
        self.metrics.set_gauge("engine.prewarm_s", self.stats.prewarm_s)
        # Two host staging banks per bucket, allocated once and alternated
        # between consecutive dispatches (ping-pong at serving granularity).
        self._banks: Dict[int, List[np.ndarray]] = {
            b: [
                np.zeros((b, *self.in_shape), self.dtype),
                np.zeros((b, *self.in_shape), self.dtype),
            ]
            for b in buckets
        }
        self._bank_idx: Dict[int, int] = {b: 0 for b in buckets}
        self._queue: "queue.Queue[CNNRequest]" = queue.Queue()
        # Depth-1 handoff: at most one dispatched-but-uncompleted batch,
        # as (device value, requests, batch id, bucket, close time in ns).
        self._inflight: (
            "queue.Queue[Tuple[jax.Array, List[CNNRequest], int, int, int]]"
        ) = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._rid = 0
        # Guards the request counter and, with ``_failure``, the handover
        # between submit() and a failing worker: a request is either queued
        # before the failure (and failed by the drain) or refused.
        self._lock = threading.Lock()
        self._failure: Optional[BaseException] = None

    # -- constructors ----------------------------------------------------------

    @staticmethod
    def _dp_policy(mesh):
        """mesh (or None) → DataParallelPolicy (or None), validated."""
        if mesh is None:
            return None
        from repro.sharding.policy import DataParallelPolicy

        return DataParallelPolicy(mesh)

    @classmethod
    def from_graph(cls, graph, plan, params, *, mesh=None, **kw) -> "CNNEngine":
        """Float engine for a (graph, plan) pair — DAG graphs through the
        segment-compiled DAG executor, sequential graphs through the
        stacked-weight scan executor.  ``mesh`` (a 1-D ``('data',)`` device
        mesh, e.g. ``launch.mesh.make_data_mesh()``) shards every bucket
        batch over the mesh.  ``persistent_cache=True`` turns on JAX's disk
        compilation cache (``serve.step.enable_persistent_cache``) so a
        fresh replica's ladder prewarm hits the cache instead of
        re-lowering."""
        dp = cls._dp_policy(mesh)
        if isinstance(graph, DAGGraph):
            fn = pingpong.make_dag_executor(graph, plan, data_parallel=dp)
        else:
            fn = pingpong.make_scan_executor(graph, plan, data_parallel=dp)
        return cls(fn, params, _input_shape(graph), jnp.float32,
                   data_parallel=dp, **kw)

    @classmethod
    def from_quantized(cls, qm, plan, *, mesh=None, **kw) -> "CNNEngine":
        """Int8 engine for a quantized model: a genuine int8 request path
        (int8 wire format, int8 arena banks) at 1/4 the float bytes.
        ``mesh`` shards bucket batches and ``persistent_cache`` enables
        the disk compilation cache, as in :meth:`from_graph`."""
        from repro.quant.exec import make_int8_executor

        dp = cls._dp_policy(mesh)
        fn, params = make_int8_executor(qm, plan, data_parallel=dp)
        return cls(fn, params, _input_shape(qm.graph), jnp.int8,
                   data_parallel=dp, **kw)

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> "CNNEngine":
        if self._threads:
            return self
        self._stop.clear()
        self._threads = [
            threading.Thread(target=self._dispatch_loop, daemon=True,
                             name="cnn-engine-dispatch"),
            threading.Thread(target=self._complete_loop, daemon=True,
                             name="cnn-engine-complete"),
        ]
        for t in self._threads:
            t.start()
        return self

    def stop(self) -> None:
        """Drain outstanding work, then stop the worker threads."""
        if not self._threads:
            return
        self._queue.join()
        self._inflight.join()
        self._stop.set()
        for t in self._threads:
            t.join()
        self._threads = []

    def __enter__(self) -> "CNNEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- request path ----------------------------------------------------------

    def submit(self, x: np.ndarray) -> CNNRequest:
        """Enqueue one image; returns a handle with ``.result(timeout)``."""
        if not self._threads:
            raise RuntimeError("engine not started (use `with engine:`)")
        x = np.asarray(x, self.dtype)
        if x.shape != self.in_shape:
            raise ValueError(f"request shape {x.shape} != {self.in_shape}")
        with self._lock:
            if self._failure is not None:
                raise RuntimeError("engine failed") from self._failure
            rid = self._rid
            self._rid += 1
            req = CNNRequest(rid=rid, x=x, t_submit=time.perf_counter())
            self._queue.put(req)
        return req

    def serve(
        self,
        images: np.ndarray,
        arrivals_s: Optional[Sequence[float]] = None,
    ) -> Tuple[List[CNNRequest], ServeStats]:
        """Replay a trace: submit ``images[i]`` at ``arrivals_s[i]`` (seconds
        from the start; ``None`` = all at once), wait for completion, and
        return (requests, stats for this run)."""
        # Consistent under the stats lock: the completer thread appends to
        # latencies_s concurrently, so both the `before` watermark and the
        # final slice go through the locked accessors (the pre-obs code read
        # len() and sliced bare — the ServeStats cross-thread race).
        before = self.stats.latency_count()
        t0 = time.perf_counter()
        reqs = []
        for i in range(len(images)):
            if arrivals_s is not None:
                delay = t0 + arrivals_s[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            reqs.append(self.submit(images[i]))
        for r in reqs:
            r.result(timeout=120.0)
        snap = self.stats.snapshot()
        run = dataclasses.replace(
            snap,
            requests=len(reqs),
            latencies_s=snap.latencies_s[before:],
            wall_s=time.perf_counter() - t0,
            compiles=self._cache.misses,
        )
        return reqs, run

    # -- worker loops ----------------------------------------------------------

    def _fail(self, error: BaseException, batch: List[CNNRequest]) -> None:
        """Fail ``batch`` and every queued request with ``error``; from now
        on ``submit`` refuses work.  Called by either worker thread."""
        for r in batch:
            r._fail(error)
        with self._lock:
            if self._failure is None:
                self._failure = error
            while True:
                try:
                    r = self._queue.get_nowait()
                except queue.Empty:
                    break
                r._fail(error)
                self._queue.task_done()

    def _coalesce(self) -> Tuple[List[CNNRequest], int]:
        """Take one batch off the queue under the coalescing policy; returns
        it with the ``time.time_ns()`` stamp of the first request's take."""
        try:
            first = self._queue.get(timeout=0.01)
        except queue.Empty:
            return [], 0
        t_first = time.time_ns()
        batch = [first]
        deadline = time.perf_counter() + self.policy.max_wait_s
        while len(batch) < self.policy.max_batch:
            timeout = deadline - time.perf_counter()
            if timeout <= 0:
                # past the deadline: take only what is already queued
                try:
                    batch.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            else:
                try:
                    batch.append(self._queue.get(timeout=timeout))
                except queue.Empty:
                    break
        return batch, t_first

    def _dispatch_loop(self) -> None:
        self.tracer.name_thread("cnn-engine-dispatch")
        while not (self._stop.is_set() and self._queue.empty()):
            batch, t_first = self._coalesce()
            if not batch:
                continue
            try:
                self._dispatch(batch, t_first)
            except Exception as e:
                self._fail(e, batch)
            finally:
                for _ in batch:
                    self._queue.task_done()

    def _dispatch(self, batch: List[CNNRequest], t_first: int) -> None:
        t_close = time.time_ns()  # the batch is closed: queue wait ends
        if self._failure is not None:
            raise self._failure  # coalesced just before a failure drained
        tr = self.tracer  # re-read: callers may enable tracing mid-run
        n = len(batch)
        bucket, compiled = self._cache.for_batch(n)
        bid = self.stats.record_batch(bucket, n)
        if tr.enabled:
            tr.complete("coalesce", t_first, t_close, batch=bid, n=n)
            tr.counter("queue_depth", depth=self._queue.qsize())
            tr.counter("batch_occupancy", n=n, bucket=bucket)
        # alternate the two staging banks for this bucket
        idx = self._bank_idx[bucket]
        self._bank_idx[bucket] = 1 - idx
        bank = self._banks[bucket][idx]
        with tr.span("stage", batch=bid, bucket=bucket, n=n):
            for i, r in enumerate(batch):
                bank[i] = r.x
            if n < bucket:
                bank[n:] = 0
        # Asynchronous dispatch: the device value is handed to the
        # completer; this thread returns to coalescing batch k+1 while
        # the device computes batch k.  Under a mesh, H2D is a sharded
        # device_put: each device receives only its batch shard.
        with tr.span("dispatch", batch=bid, bucket=bucket, n=n):
            with tr.span("h2d", batch=bid):
                # From a copy: on the CPU a device array may alias its host
                # buffer, and this bank is restaged two batches on, while
                # this batch may still be running.
                host = bank.copy()
                if self.data_parallel is not None:
                    x = jax.device_put(
                        host, self.data_parallel.batch_sharding())
                else:
                    x = jnp.asarray(host)
            with tr.span("launch", batch=bid):
                y = compiled(self.params, x)
        # Blocks while the completer still holds the previous batch.
        with tr.span("handoff", batch=bid):
            self._inflight.put((y, batch, bid, bucket, t_close))

    def _complete_loop(self) -> None:
        self.tracer.name_thread("cnn-engine-complete")
        while not (self._stop.is_set() and self._inflight.empty()):
            try:
                y, batch, bid, bucket, t_close = self._inflight.get(
                    timeout=0.01)
            except queue.Empty:
                continue
            try:
                self._complete(y, batch, bid, bucket, t_close)
            except Exception as e:
                self._fail(e, batch)
            finally:
                self._inflight.task_done()

    def _complete(self, y, batch: List[CNNRequest], bid: int, bucket: int,
                  t_close: int) -> None:
        tr = self.tracer
        with tr.span("device", batch=bid, bucket=bucket, n=len(batch)):
            out = np.asarray(y)  # blocks until the device value is ready
        with tr.span("complete", batch=bid, bucket=bucket, n=len(batch)):
            t_done = time.perf_counter()
            for i, r in enumerate(batch):
                r.y = out[i]
                r.t_done = t_done
                r._done.set()
        self.stats.record_latencies(r.latency_s for r in batch)
        if tr.enabled:
            # One record per batch: each request's queue wait (submit →
            # batch closed) and lifetime, written off the submitter's thread.
            tr.requests([r.rid for r in batch],
                        [tr.perf_ns(r.t_submit) for r in batch],
                        t_close, tr.perf_ns(t_done), batch=bid, bucket=bucket)


# ---------------------------------------------------------------------------
# Streaming session mode (per-frame KWS serving)
# ---------------------------------------------------------------------------


class StreamServer:
    """Session-mode serving for the streaming executor (DESIGN.md §13).

    A KWS deployment holds one open audio stream per client and consumes
    one MFCC frame at a time; the unit of serving state is therefore a
    *session*, not a request.  This server keeps one ring-state pytree per
    stream id — all streams share the single AOT-prewarmed per-frame step
    (``StreamingExecutor.aot_step``), compiled once at construction, so
    opening a stream costs one ``init_state`` call and pushing a frame one
    pre-compiled dispatch.  ``push`` returns the new classification on
    emitting frames (every ``emit_stride``-th — 2 for ``ds_cnn()``) and
    ``None`` in between; ``peek`` reads the stream's held output.

    Whether a frame emits depends only on how many frames the stream has
    pushed since ``open``, so the host keeps that count beside each
    stream's device state and decides itself: the device still computes
    ``phase`` and ``emitted`` for its own ``lax.cond``, but the host never
    reads them.  A non-emitting ``push`` launches the step and returns
    without waiting on the device; an emitting one waits once, for the
    single D2H of the posterior, queued right behind the step.

    Numerics follow the wrapped executor: :meth:`from_quantized` serves the
    int8 step (int8 frames on the wire, quantize with
    ``quantize.quantize_input``), :meth:`from_graph` the float step.
    ``persistent_cache=True`` enables the disk compilation cache exactly as
    on :class:`CNNEngine`.  An enabled ``tracer`` records each ``push`` as a
    span on the caller's thread with its ``h2d`` and ``step`` parts and, on
    emitting frames only, ``d2h`` (the wait for the step and the transfer).
    ``metrics`` counts ``stream.frames``, ``stream.emissions`` and
    ``stream.device_waits`` (pushes that blocked on the device: one per
    emission).
    """

    def __init__(
        self,
        executor,
        params,
        *,
        prewarm: bool = True,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        persistent_cache: bool = False,
    ):
        if persistent_cache:
            enable_persistent_cache()
        self.executor = executor
        self.params = params
        # Read per push, so a caller may swap in an enabled Tracer.
        self.tracer = tracer or NULL_TRACER
        self.metrics = metrics or MetricsRegistry("stream_server")
        t0 = time.perf_counter()
        self._step = executor.aot_step(params) if prewarm else executor.step
        self.prewarm_s = time.perf_counter() - t0 if prewarm else 0.0
        self.metrics.set_gauge("stream.prewarm_s", self.prewarm_s)
        self._emit_stride = executor.splan.emit_stride
        # stream id -> (device state, frames pushed since open)
        self._states: Dict[str, Tuple[dict, int]] = {}
        self._lock = threading.Lock()

    # -- constructors ----------------------------------------------------------

    @classmethod
    def from_graph(cls, graph, params, *, splan=None, **kw) -> "StreamServer":
        """Float streaming server for a chain graph (plans the ring arena
        via ``streaming.plan_streaming`` unless ``splan`` is given)."""
        from repro.core import streaming

        ex = streaming.make_streaming_executor(graph, splan)
        return cls(ex, params, **kw)

    @classmethod
    def from_quantized(cls, qm, *, splan=None, **kw) -> "StreamServer":
        """Int8 streaming server: int8 frames in, int8 logits out,
        bit-exact vs the sliding full-window oracle."""
        from repro.quant.exec import make_int8_streaming_executor

        ex, params = make_int8_streaming_executor(qm, splan)
        return cls(ex, params, **kw)

    # -- session API -----------------------------------------------------------

    @property
    def streams(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(self._states)

    def open(self, stream_id: str) -> None:
        """Open a stream with zero-history warm-start state."""
        with self._lock:
            if stream_id in self._states:
                raise ValueError(f"stream {stream_id!r} already open")
            self._states[stream_id] = (self.executor.init_state(self.params), 0)
        self.metrics.inc("stream.opened")

    def push(self, stream_id: str, frame: np.ndarray) -> Optional[np.ndarray]:
        """Feed one (C, W) frame; returns the new output on emitting frames,
        ``None`` otherwise.  Unknown stream ids are opened implicitly.

        The n-th frame since ``open`` emits when ``n % emit_stride == 0``,
        decided on the host.  A non-emitting push returns as soon as the
        step is launched, before it has run on the device; an emitting one
        blocks once, on the D2H of the posterior.  The frame is copied
        before the H2D, so the caller may reuse its buffer on return."""
        tr = self.tracer
        with tr.span("push", stream=stream_id):
            with self._lock:
                entry = self._states.get(stream_id)
            if entry is None:
                self.open(stream_id)
                with self._lock:
                    entry = self._states[stream_id]
            state, n = entry
            n += 1
            with tr.span("h2d"):
                frame = jnp.asarray(np.array(frame, self.executor.dtype, copy=True))
            with tr.span("step"):
                state, out, _ = self._step(self.params, state, frame)
            emits = n % self._emit_stride == 0
            if emits:
                out.copy_to_host_async()
            with self._lock:
                self._states[stream_id] = (state, n)
            self.metrics.inc("stream.frames")
            if not emits:
                return None
            self.metrics.inc("stream.emissions")
            self.metrics.inc("stream.device_waits")
            with tr.span("d2h"):
                return np.asarray(out)

    def peek(self, stream_id: str) -> np.ndarray:
        """The stream's held output (last emission; zero-window head output
        before the first)."""
        with self._lock:
            return np.asarray(self._states[stream_id][0]["out"])

    def close(self, stream_id: str) -> np.ndarray:
        """Close a stream, returning its final held output."""
        with self._lock:
            state, _ = self._states.pop(stream_id)
        self.metrics.inc("stream.closed")
        return np.asarray(state["out"])
