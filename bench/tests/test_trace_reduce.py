"""``trace_reduce`` on a small trace recorded on one TPU v5e chip
(``record_trace.py``: three runs of a jitted matmul chain, each after a 2 ms
``bench.wait``). Expected values read by hand from the events it printed.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "testdata" / "tiny_trace.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(trace_reduce.events(str(TRACE)))


def test_trace_is_small():
    assert TRACE.stat().st_size < 1 << 20


def test_window_and_busy(summary):
    # bench.window: 44523499 .. 53779528 ns.
    assert summary["window_s"] == pytest.approx(9_256_029e-9, abs=1e-12)
    # Union of the 12 XLA ops: 24903 + 24686 + 24892 ns.
    assert summary["busy_s"] == pytest.approx(74_481e-9, abs=1e-12)
    assert trace_reduce.idle_pct(summary) == pytest.approx(
        100 * (1 - 74_481 / 9_256_029))


def test_modules(summary):
    # Three runs of jit__lambda: 24915 + 24698 + 24902 ns.
    assert summary["modules"] == {"jit__lambda": {"s": pytest.approx(74_515e-9), "n": 3}}


def test_device_ops(summary):
    ops = dict(summary["device_ops"])
    assert ops["fusion f32[1024,1024]"] == pytest.approx(39_853e-9)
    assert ops["convolution_tanh_fusion bf16[1024,1024]"] == pytest.approx(34_581e-9)
    assert summary["device_ops"][0][0] == "fusion f32[1024,1024]"


def test_idle_gaps_by_host_span(summary):
    gaps = dict(summary["idle_gaps"])
    # Between the runs the host sat in bench.d2h: 3210745 + 3172913 ns.
    assert gaps["bench.d2h"] == pytest.approx(6_383_658e-9)
    # Before the first run, after the last, and 12 ns of small gaps inside
    # runs that fall in a bench.wait.
    assert gaps["bench.wait"] == pytest.approx(2_797_890e-9)
    assert sum(gaps.values()) == pytest.approx(summary["window_s"] - summary["busy_s"])


def test_no_window_raises():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"host": [], "devices": {0: {"ops": [], "modules": []}}})
