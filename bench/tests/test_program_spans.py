"""``program_spans``: program spans laid over ``tiny_trace.xplane.pb`` (a
trace recorded on one TPU v5e chip) through its ``profile_start_time``, and
the readers of the program-span metrics."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = BENCH / "testdata" / "tiny_trace.xplane.pb"
NEW_METRICS = ("engine.queue_wait_ms", "engine.host_ms_per_batch",
               "device_idle.engine_work.serve", "stream.step_ms", "stream.sync_ms")


@pytest.fixture(scope="module")
def trace():
    ev = trace_reduce.events(str(TRACE))
    return ev, program_spans.profile_start_ns(str(TRACE)), trace_reduce.reduce(ev)


def _longest_gaps(ev, n):
    """The ``n`` longest device-idle gaps, rounded down to whole trace ns
    (the tracer's stamps are ints: a float near ``profile_start_time`` keeps
    256 ns steps), so they match the trace's own to 1 ns."""
    gaps = sorted(program_spans._idle(ev), key=lambda g: g[0] - g[1])[:n]
    return [(int(s), int(e)) for s, e in gaps]


def test_profile_start_time(trace):
    _, start, _ = trace
    assert start == 1792187208242798397


def test_gaps_go_to_the_span_over_their_middle(trace):
    ev, start, summary = trace
    (a0, a1), (b0, b1) = _longest_gaps(ev, 2)  # between runs

    def at(s, e):  # trace ns -> the tracer's epoch ns
        return s + start, e + start

    spans = [
        # the dispatcher works over the first gap's middle, inside a parent
        ("dispatch", 1, *at(a0, a1), {"batch": 0}),
        ("h2d", 1, *at(a0 + 10, a1 - 10), {"batch": 0}),
        # the completer waits on the device over both gaps
        ("device", 2, *at(a0 - 5, b1 + 5), {"batch": 0}),
        # a request's lifetime labels nothing
        ("request", 1, *at(b0, b1), {"closed_ns": b0 + start, "batch": 0}),
    ]
    got = dict(program_spans.program_gaps(ev, spans, start))
    assert got["h2d"] == pytest.approx((a1 - a0) * 1e-9, abs=2e-9)
    # gap b and the few-ns gaps between ops of the run before it
    waited = sum(e - s for s, e in program_spans._idle(ev) if a1 <= (s + e) / 2 < b1 + 5)
    assert waited - (b1 - b0) < 20
    assert got["device"] == pytest.approx(waited * 1e-9, abs=2e-9)
    assert set(got) == {"h2d", "device", program_spans.IDLE}
    assert sum(got.values()) == pytest.approx(summary["window_s"] - summary["busy_s"],
                                              rel=1e-9)


def test_idle_gaps_cover_the_idle_time(trace):
    ev, _, summary = trace
    idle = sum(e - s for s, e in program_spans._idle(ev)) * 1e-9
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"], rel=1e-9)


def test_work_idle_is_exact_coverage(trace):
    ev, start, _ = trace
    (a0, a1), = _longest_gaps(ev, 1)
    spans = [("stage", 1, a0 + 100 + start, a0 + 400 + start, {"batch": 0}),
             # overlapping work on two threads counts once
             ("complete", 2, a0 + 300 + start, a0 + 700 + start, {"batch": 0}),
             # work that runs on past the gap into device time
             ("launch", 1, a1 - 50 + start, a1 + 50 + start, {"batch": 1}),
             ("device", 2, a0 + start, a1 + start, {"batch": 0})]  # a wait
    assert program_spans.work_idle_s(ev, spans, start) == pytest.approx(650e-9, abs=4e-9)


def test_parent_self_time_is_work(trace):
    ev, start, _ = trace
    (a0, a1), = _longest_gaps(ev, 1)
    mid = (a0 + a1) // 2
    spans = [("push", 7, a0 + start, a1 + start, {"stream": "s0"}),
             ("step", 7, a0 + start, mid - 100 + start, {}),
             ("sync", 7, mid + 100 + start, a1 + start, {})]
    assert dict(program_spans.program_gaps(ev, spans, start))["push"] == pytest.approx(
        (a1 - a0) * 1e-9, abs=2e-9)


def test_existing_numbers_unchanged(trace):
    _, _, summary = trace
    assert summary["window_s"] == pytest.approx(9_256_029e-9, abs=1e-12)
    assert summary["busy_s"] == pytest.approx(74_481e-9, abs=1e-12)


def _record(dropped):
    spans = []
    for b in range(3):
        t = b * 10_000_000
        spans += [("coalesce", 1, t, t + 2_000_000, {"batch": b}),
                  ("stage", 1, t + 2_000_000, t + 2_100_000, {"batch": b}),
                  ("h2d", 1, t + 2_100_000, t + 2_200_000, {"batch": b}),
                  ("launch", 1, t + 2_200_000, t + 2_300_000, {"batch": b}),
                  ("complete", 2, t + 3_000_000, t + 3_100_000, {"batch": b}),
                  ("request", 2, t, t + 3_100_000, {"batch": b, "closed_ns": t + 2_000_000}),
                  ("step", 3, t, t + 400_000, {}),
                  ("sync", 3, t + 400_000, t + 500_000, {})]
    return {"spans": spans, "dropped": dropped,
            "program_gaps": [["stage", 0.001], ["device", 0.002], [program_spans.IDLE, 0.007]],
            "work_idle_s": 0.001,
            "trace": {"window_s": 0.01, "busy_s": 0.0}}


def test_readers():
    rec = _record(0)
    got = {n: run.reader(n)(rec) for n in NEW_METRICS}
    assert got == pytest.approx({"engine.queue_wait_ms": 2.0, "engine.host_ms_per_batch": 0.4,
                                 "device_idle.engine_work.serve": 10.0,
                                 "stream.step_ms": 0.4, "stream.sync_ms": 0.1})


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_refuses_a_ring_that_dropped(name):
    assert run.reader(name)(_record(1)) is None
    assert run.reader(name)({"trace": {"window_s": 1.0, "busy_s": 0.0}}) is None
