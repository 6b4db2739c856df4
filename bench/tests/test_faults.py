"""A whole run with the timed path broken underneath must come out not
correct; the same run unbroken comes out correct.

Skips the harness's look for a chip (``require_tpu=False``) and shrinks the
traffic so that the CPU holds it; everything else is the run as the
benchmark's command makes it. Faults, each planted in the compiled program the window drives:

* ``half``: the second half of every batch left out (its rows repeat the
  first half's), for the bulk and serving cells;
* ``altered``: one logit of every answer off by one step where it is
  produced;
* ``stale``: the stream step returns its state unchanged (streams).
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import loadgen  # noqa: E402
import run  # noqa: E402
from repro.core import streaming  # noqa: E402
from repro.quant import exec as qexec  # noqa: E402

SMALL = {
    "vww_int8.bulk": {"batch": 4, "pool_batches": 2, "check_rows": 8},
    "kws_int8.bulk": {"batch": 8, "pool_batches": 2, "check_rows": 16},
    "kws_int8.serve": {"rate_per_s": 300, "check_rows": 64},
    "kws_int8.streams": {"streams": 3, "check_rows": 16},
}
SEED = 2**36 + 11


def _broken_executor(fault):
    real = qexec.make_int8_executor

    def make(qm, plan, **kw):
        fn, params = real(qm, plan, **kw)

        @jax.jit
        def broken(p, x):
            y = fn(p, x)
            if fault == "half":
                n = y.shape[0]
                keep = (n + 1) // 2
                return y.at[keep:].set(y[: n - keep])
            return y.at[..., 0].add(1)

        return broken, params

    return make


def _broken_step(fault):
    real = streaming.StreamingExecutor._step_impl

    def step(self, params, state, frame):
        new_state, out, emitted = real(self, params, state, frame)
        if fault == "stale":
            return state, out, emitted
        return new_state, out.at[..., 0].add(1), emitted

    return step


def _run(workload):
    result, window = run.run(workload, SEED, 1.0, False, require_tpu=False,
                             traffic_override=SMALL[workload])
    assert window["compiles_in_window"] == 0
    return result


CASES = [
    ("kws_int8.bulk", "half"), ("kws_int8.bulk", "altered"),
    ("vww_int8.bulk", "half"), ("vww_int8.bulk", "altered"),
    ("kws_int8.serve", "half"), ("kws_int8.serve", "altered"),
    ("kws_int8.streams", "stale"), ("kws_int8.streams", "altered"),
]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    if fault == "stale" or workload.endswith("streams"):
        monkeypatch.setattr(streaming.StreamingExecutor, "_step_impl", _broken_step(fault))
    else:
        monkeypatch.setattr(loadgen, "make_int8_executor", _broken_executor(fault))
        monkeypatch.setattr(qexec, "make_int8_executor", _broken_executor(fault))
    result = _run(workload)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
