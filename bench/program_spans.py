"""The program's own spans (``repro.obs.trace.Tracer``) laid over a profiler
trace, and the per-layer numbers read from them.

The tracer stamps its events with ``time.time_ns()``, the epoch clock the
JAX profiler stamps its own with, and the trace's ``Task Environment``
plane holds ``profile_start_time`` on that clock: a span maps onto the
trace's timeline by one subtraction.

* :func:`record`: the spans of a window and the tracer's ``dropped`` count,
  the keys a run's record holds for the readers below.
* :func:`program_gaps`: device idle time in the window, summed by the
  program span that covers the middle of each gap: a work span over a
  wait span (``WAIT``), and ``"engine: idle"`` where none does.
* :func:`work_idle_s`: device idle time in the window during which some
  thread of the program was in a work span, to the nanosecond.
* The readers of ``metrics/engine.*``, ``metrics/stream.step_ms``,
  ``metrics/stream.sync_ms`` and ``metrics/device_idle.engine_work.serve``;
  each reads ``None`` from a record without spans or with ``dropped > 0``.
"""
from __future__ import annotations

import bisect
import collections
import statistics

import trace_reduce

# Spans in which a thread waits on another thread or on the device; every
# other span of the program is host work.  ``request`` spans are request
# lifetimes, not thread activity.
WAIT = frozenset({"handoff", "device", "coalesce"})
LIFETIMES = frozenset({"request"})
IDLE = "engine: idle"


def profile_start_ns(path: str) -> int:
    """``profile_start_time`` (epoch ns) of an ``.xplane.pb`` trace."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise ValueError(f"{path}: no Task Environment plane")


def record(tracer, t0_ns: int, t1_ns: int) -> dict:
    """``{"spans": [...], "dropped": n}``: the tracer's spans that lie in
    ``[t0_ns, t1_ns]`` (``time.time_ns()`` stamps), as
    ``(name, tid, start_ns, end_ns, args)``."""
    spans = [s for s in tracer.spans() if t0_ns <= s[2] and s[3] <= t1_ns]
    return {"spans": spans, "dropped": tracer.dropped}


def _innermost(spans):
    """Per thread, ``(starts, pieces)``: the thread's spanned time cut into
    ``(start, end, name)`` pieces, each named by the innermost span that
    covers it (a thread's spans nest)."""
    by_tid = collections.defaultdict(list)
    for name, tid, s, e, _ in spans:
        if name not in LIFETIMES:
            by_tid[tid].append((s, -e, name))
    out = []
    for items in by_tid.values():
        pieces, stack, t = [], [], 0  # stack: (end, name) of open spans
        for s, neg_e, name in sorted(items):
            while stack and stack[-1][0] <= s:
                end, outer = stack.pop()
                pieces.append((t, end, outer))
                t = end
            if stack:
                pieces.append((t, s, stack[-1][1]))
            stack.append((-neg_e, name))
            t = s
        while stack:
            end, outer = stack.pop()
            pieces.append((t, end, outer))
            t = end
        pieces = [p for p in pieces if p[1] > p[0]]
        out.append(([p[0] for p in pieces], pieces))
    return out


def _idle(ev: dict):
    """The device's idle intervals in the ``bench.window`` of ``ev``
    (:func:`trace_reduce.events`), on the first chip, trace ns."""
    (w0, w1), = [(s, e) for n, s, e in ev["host"] if n == trace_reduce.WINDOW]
    dev = ev["devices"][min(ev["devices"])]["ops"]
    busy = trace_reduce._union([(max(s, w0), min(e, w1)) for _, s, e in dev
                                if e > w0 and s < w1])
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def program_gaps(ev: dict, spans, start_ns: int) -> list:
    """Device idle time in the window of ``ev``, as ``[[label, seconds]]``
    largest first: each gap goes to the program span (epoch-ns ``spans``,
    mapped by the trace's ``start_ns``) that covers its middle, a work span
    before a ``WAIT`` span, else to ``"engine: idle"``. The labels sum to
    the window's idle time on the first chip."""
    mapped = [(n, tid, s - start_ns, e - start_ns, a) for n, tid, s, e, a in spans]
    threads = _innermost(mapped)
    gaps = collections.Counter()
    for s, e in _idle(ev):
        mid = (s + e) / 2
        work = wait = None
        for starts, pieces in threads:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and pieces[i][1] > mid:
                name = pieces[i][2]
                if name in WAIT:
                    wait = wait or name
                else:
                    work = work or name
        gaps[work or wait or IDLE] += e - s
    return [[n, t * 1e-9] for n, t in gaps.most_common()]


def work_idle_s(ev: dict, spans, start_ns: int) -> float:
    """Seconds of device idle time in the window of ``ev`` during which a
    thread of the program was in a work span (any span outside ``WAIT``)."""
    work = trace_reduce._union([(s - start_ns, e - start_ns) for n, _, s, e, _ in spans
                                if n not in WAIT and n not in LIFETIMES])
    total, i = 0, 0
    for s, e in _idle(ev):  # both lists sorted and disjoint
        while i < len(work) and work[i][1] <= s:
            i += 1
        j = i
        while j < len(work) and work[j][0] < e:
            total += min(e, work[j][1]) - max(s, work[j][0])
            j += 1
    return total * 1e-9


def _spans(rec, *names):
    """The record's spans of ``names``, or None where the record has no
    spans or the tracer dropped any."""
    if not rec.get("spans") or rec.get("dropped", 1) > 0:
        return None
    return [s for s in rec["spans"] if s[0] in names]


def median_ms(rec, name: str):
    """Median length in ms of the record's ``name`` spans."""
    spans = _spans(rec, name)
    if not spans:
        return None
    return statistics.median(e - s for _, _, s, e, _ in spans) * 1e-6


def queue_wait_ms(rec):
    """Median over the window's requests of submit -> batch closed, in ms."""
    reqs = _spans(rec, "request")
    if not reqs:
        return None
    return statistics.median(a["closed_ns"] - s for _, _, s, _, a in reqs) * 1e-6


HOST_WORK = ("stage", "h2d", "launch", "complete")


def host_ms_per_batch(rec):
    """Median per batch of the engine's host work (``HOST_WORK``, spans
    with no child span), in ms, over the batches that have all four."""
    spans = _spans(rec, *HOST_WORK)
    if not spans:
        return None
    per = collections.defaultdict(dict)
    for name, _, s, e, a in spans:
        per[a["batch"]][name] = e - s
    whole = [sum(v.values()) for v in per.values() if len(v) == len(HOST_WORK)]
    return statistics.median(whole) * 1e-6 if whole else None


def engine_work_idle_pct(rec):
    """Device-idle time covered by an engine work span
    (:func:`work_idle_s`), in % of the window."""
    if _spans(rec) is None or "work_idle_s" not in rec or not rec.get("trace"):
        return None
    return 100.0 * rec["work_idle_s"] / rec["trace"]["window_s"]
