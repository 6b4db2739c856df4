"""Streaming executor tests (DESIGN.md §13): ring-extent planning, the
per-frame step vs the sliding full-window oracle (f32 to tolerance, int8
bit-exact — warm-up transient included), the streaming session server, the
static per-frame cost model, and the persistent compilation cache.

The independent oracle is :func:`streaming.sliding_window_reference`: a
full-window forward over the last H rows of ``zeros ++ frames[:t+1]`` at
every emitting frame — zero prehistory, exactly the executor's
``init_state`` semantics.  Int8 runs the oracle through
``quantize.simulate_int8_dag_forward`` (the eager §5 simulator), so the
streaming path is never tested against itself.
"""
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import nn, quantize, streaming
from repro.core.graph import (
    Conv2d,
    DAGGraph,
    DepthwiseConv2d,
    Flatten,
    Input,
    Linear,
    MaxPool2d,
    ReLU,
    SequentialGraph,
    ds_cnn,
)
from repro.core.planner import verify_plan
from repro.obs import report
from repro.quant import exec as qexec

# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ds():
    g = ds_cnn()
    params = nn.init_params(g.to_sequential(), jax.random.PRNGKey(0))
    calib = jax.random.normal(jax.random.PRNGKey(1), (1, 49, 10))
    qm = quantize.quantize_dag(g, params, calib)
    return g, params, qm


def random_stream_chain(seed: int):
    """A seeded random streamable chain + frames (the non-hypothesis half of
    the property: random conv/dw/pool prefixes, ReLU views, FC head)."""
    rng = np.random.default_rng(seed)
    c, h, w = int(rng.integers(1, 4)), int(rng.integers(10, 17)), 6
    layers = [Input(shape=(c, h, w), name="input")]
    ch, hh, ww = c, h, w
    for i in range(int(rng.integers(1, 4))):
        kind = rng.choice(["conv", "dw", "pool"])
        if kind == "conv":
            k = int(rng.choice([1, 3]))
            s = int(rng.choice([1, 2]))
            p = int(rng.integers(0, k))
            oc = int(rng.integers(2, 6))
            layer = Conv2d(ch, oc, kernel_size=k, stride=s, padding=p,
                           name=f"conv{i}")
        elif kind == "dw":
            k, s = 3, 1
            p = int(rng.integers(0, 2))
            oc = ch
            layer = DepthwiseConv2d(ch, kernel_size=k, stride=s, padding=p,
                                    name=f"dw{i}")
        else:
            k = int(rng.choice([2, 3]))
            s = int(rng.choice([1, 2]))
            p = 0
            oc = ch
            layer = MaxPool2d(kernel_size=k, stride=s, name=f"pool{i}")
        oh = (hh + 2 * p - k) // s + 1
        ow = (ww + 2 * p - k) // s + 1
        if oh < 2 or ow < 1:
            break
        layers.append(layer)
        if kind != "pool" and rng.random() < 0.7:
            layers.append(ReLU(name=f"relu{i}"))
        ch, hh, ww = oc, oh, ow
    layers += [Flatten(name="flatten"),
               Linear(ch * hh * ww, 4, name="fc")]
    g = SequentialGraph(layers)
    g.validate()
    n_frames = int(rng.integers(5, 11))
    frames = np.asarray(rng.standard_normal((n_frames, c, w)), np.float32)
    return g, frames


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------


def test_plan_streaming_ds_cnn_extents(ds):
    g, _, _ = ds
    splan = streaming.plan_streaming(g, io_dtype_bytes=1)
    assert splan.emit_stride == 2  # the stride-2 stem
    assert splan.head == ("pool", "fc")  # full recompute only for pool+FC
    names = [r.name for r in splan.rings]
    assert names == ["conv1", "dw1", "pw1", "dw2", "pw2", "dw3", "pw3",
                     "dw4", "pw4"]
    # ring extents from the receptive-field growth derivation (DESIGN.md §13)
    assert [r.rows for r in splan.rings] == [23, 21, 21, 19, 19, 17, 17, 15, 15]
    assert [r.top for r in splan.rings] == [1, 2, 2, 3, 3, 4, 4, 5, 5]
    assert [r.bottom for r in splan.rings] == [1, 2, 2, 3, 3, 4, 4, 5, 5]
    assert all(r.new_rows == 1 for r in splan.rings)
    # every ring can absorb its per-emission advance
    assert all(r.rows >= r.new_rows for r in splan.rings)


def test_plan_streaming_is_a_verified_memory_plan(ds):
    g, _, _ = ds
    splan = streaming.plan_streaming(g, io_dtype_bytes=1)
    assert splan.plan.strategy == "streaming-ring"
    verify_plan(splan.plan)  # live-range overlap + bounds, bank-agnostic
    banks = {b.bank for b in splan.plan.buffers}
    assert banks == {"ring", "stream"}
    # the independently-derived timeline peak must equal the declared arena
    tl = report.arena_timeline(splan.plan)
    assert tl["peak_bytes"] == tl["arena_bytes"] == splan.plan.arena_bytes
    # persistent ring state is a subset of the arena
    assert splan.ring_elems < splan.plan.arena_elems


def test_plan_streaming_random_chains_verify():
    for seed in range(6):
        g, _ = random_stream_chain(seed)
        splan = streaming.plan_streaming(g)
        verify_plan(splan.plan)
        for r in splan.rings:
            assert r.rows >= r.new_rows >= 1
            assert splan.emit_stride % r.cum_stride == 0


# ---------------------------------------------------------------------------
# f32 vs the sliding full-window oracle
# ---------------------------------------------------------------------------


def test_streaming_f32_matches_sliding_oracle_ds_cnn(ds):
    g, params, _ = ds
    ex = streaming.make_streaming_executor(g)
    state = ex.init_state(params)
    frames = np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (9, 1, 10)), np.float32)
    ref_outs, ref_em = streaming.sliding_window_reference(g, params, frames)
    for t in range(frames.shape[0]):  # warm-up transient included
        state, out, em = ex.step(params, state, jnp.asarray(frames[t]))
        assert bool(em) == bool(ref_em[t])
        np.testing.assert_allclose(np.asarray(out), ref_outs[t],
                                   rtol=1e-4, atol=1e-4)


def test_streaming_run_scan_matches_step(ds):
    g, params, _ = ds
    ex = streaming.make_streaming_executor(g)
    frames = jax.random.normal(jax.random.PRNGKey(3), (8, 1, 10))
    _, outs, em = ex.run(params, ex.init_state(params), frames)
    state = ex.init_state(params)
    for t in range(8):
        state, out, e = ex.step(params, state, frames[t])
        assert bool(e) == bool(em[t])
        np.testing.assert_allclose(np.asarray(outs[t]), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)


def test_streaming_f32_random_chains_match_oracle():
    for seed in (0, 1, 2):
        g, frames = random_stream_chain(seed)
        params = nn.init_params(g, jax.random.PRNGKey(seed))
        ex = streaming.make_streaming_executor(g)
        state = ex.init_state(params)
        ref_outs, ref_em = streaming.sliding_window_reference(g, params, frames)
        for t in range(frames.shape[0]):
            state, out, em = ex.step(params, state, jnp.asarray(frames[t]))
            assert bool(em) == bool(ref_em[t])
            np.testing.assert_allclose(np.asarray(out), ref_outs[t],
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# int8: bit-exact vs the eager simulator oracle
# ---------------------------------------------------------------------------


def test_streaming_int8_bit_exact_ds_cnn(ds):
    g, _, qm = ds
    ex, qp = qexec.make_int8_streaming_executor(qm)
    assert ex.dtype == jnp.int8
    frames_f = jax.random.normal(jax.random.PRNGKey(4), (9, 1, 10))
    frames_q = np.asarray(quantize.quantize_input(qm, frames_f))
    ref_outs, ref_em = streaming.sliding_window_reference(
        g, qp, frames_q,
        forward_fn=lambda _, w: quantize.simulate_int8_dag_forward(qm, w))
    state = ex.init_state(qp)
    for t in range(frames_q.shape[0]):
        state, out, em = ex.step(qp, state, jnp.asarray(frames_q[t]))
        assert bool(em) == bool(ref_em[t])
        assert out.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(out), ref_outs[t])


def test_streaming_int8_bit_exact_random_chains():
    for seed in (3, 5):
        g, frames = random_stream_chain(seed)
        dag = DAGGraph.from_sequential(g)
        params = nn.init_params(g, jax.random.PRNGKey(seed))
        calib = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (tuple(g.layers[0].shape)))
        qm = quantize.quantize_dag(dag, params, calib)
        ex, qp = qexec.make_int8_streaming_executor(qm)
        frames_q = np.asarray(quantize.quantize_input(qm, jnp.asarray(frames)))
        ref_outs, ref_em = streaming.sliding_window_reference(
            dag, qp, frames_q,
            forward_fn=lambda _, w: quantize.simulate_int8_dag_forward(qm, w))
        state = ex.init_state(qp)
        for t in range(frames_q.shape[0]):
            state, out, em = ex.step(qp, state, jnp.asarray(frames_q[t]))
            assert bool(em) == bool(ref_em[t])
            np.testing.assert_array_equal(np.asarray(out), ref_outs[t])


def test_streaming_int8_aot_step_bit_exact(ds):
    g, _, qm = ds
    ex, qp = qexec.make_int8_streaming_executor(qm)
    aot = ex.aot_step(qp)
    frames_q = np.asarray(quantize.quantize_input(
        qm, jax.random.normal(jax.random.PRNGKey(5), (4, 1, 10))))
    s1 = ex.init_state(qp)
    s2 = ex.init_state(qp)
    for t in range(4):
        s1, o1, e1 = ex.step(qp, s1, jnp.asarray(frames_q[t]))
        s2, o2, e2 = aot(qp, s2, jnp.asarray(frames_q[t]))
        assert bool(e1) == bool(e2)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_streaming_report_ds_cnn_mac_pins(ds):
    g, _, _ = ds
    r = report.streaming_report(g, streaming.plan_streaming(g, io_dtype_bytes=1))
    # hand-derived (DESIGN.md §13): 3 conv1 rows + 8×(dw or pw rows) + head
    assert r["full_window_macs"] == 2539840  # same total as the fused chain
    assert r["per_emission_macs"] == 775360
    assert r["per_frame_macs"] == 387680
    assert r["per_frame_frac"] == pytest.approx(0.1526, abs=1e-4)
    assert r["per_frame_frac"] <= 0.25  # the CI gate's cost-model half
    assert r["emit_stride"] == 2
    assert [row["ring_rows"] for row in r["rings"]] == [23, 21, 21, 19, 19,
                                                        17, 17, 15, 15]
    assert r["ring_arena_bytes"] > r["ring_state_bytes"] > 0


# ---------------------------------------------------------------------------
# serving session mode
# ---------------------------------------------------------------------------


def test_stream_server_multi_stream_isolation(ds):
    from repro.serve.cnn_engine import StreamServer

    g, _, qm = ds
    srv = StreamServer.from_quantized(qm)
    assert srv.prewarm_s > 0  # AOT step paid at construction
    frames_a = np.asarray(quantize.quantize_input(
        qm, jax.random.normal(jax.random.PRNGKey(6), (4, 1, 10))))
    frames_b = np.asarray(quantize.quantize_input(
        qm, jax.random.normal(jax.random.PRNGKey(7), (4, 1, 10))))
    srv.open("a")
    srv.open("b")
    got_a, got_b = [], []
    for t in range(4):  # interleaved pushes must not cross-contaminate
        got_a.append(srv.push("a", frames_a[t]))
        got_b.append(srv.push("b", frames_b[t]))
    refs = {}
    for sid, frames, got in (("a", frames_a, got_a), ("b", frames_b, got_b)):
        ref_outs, ref_em = streaming.sliding_window_reference(
            g, None, frames,
            forward_fn=lambda _, w: quantize.simulate_int8_dag_forward(qm, w))
        refs[sid] = (ref_outs, ref_em)
        for t in range(4):
            if ref_em[t]:
                np.testing.assert_array_equal(got[t], ref_outs[t])
            else:
                assert got[t] is None
    assert set(srv.streams) == {"a", "b"}
    final_a = srv.close("a")  # close returns the last held (emitted) output
    np.testing.assert_array_equal(final_a, refs["a"][0][3])
    assert srv.streams == ("b",)


def test_stream_server_implicit_open_and_peek(ds):
    from repro.serve.cnn_engine import StreamServer

    g, params, _ = ds
    srv = StreamServer.from_graph(g, params, prewarm=False)
    frame = np.zeros((1, 10), np.float32)
    out = srv.push("s", frame)  # implicit open; frame 1 of E=2 → no emission
    assert out is None
    assert srv.streams == ("s",)
    held = srv.peek("s")  # zero-window head output before the first emission
    assert held.shape == (12,)
    out = srv.push("s", frame)  # frame 2 → emission
    assert out is not None
    with pytest.raises(ValueError):
        srv.open("s")


def test_stream_server_host_emission_schedule(ds):
    """The host decides which pushes emit; it must pick exactly the frames
    the device's ``phase`` picks, and block on the device only for those."""
    from repro.serve.cnn_engine import StreamServer

    _, _, qm = ds
    srv = StreamServer.from_quantized(qm)
    ex, qp = srv.executor, srv.params
    n = 2 * ex.splan.emit_stride + 1
    frames = {sid: np.asarray(quantize.quantize_input(
        qm, jax.random.normal(jax.random.PRNGKey(k), (n, 1, 10))))
        for k, sid in ((8, "a"), (9, "b"))}
    got = {"a": [], "b": []}
    for t in range(n):
        for sid in ("a", "b"):
            got[sid].append(srv.push(sid, frames[sid][t]))
    n_emit = 0
    for sid in ("a", "b"):
        _, outs, emitted = ex.run(qp, ex.init_state(qp), jnp.asarray(frames[sid]))
        emitted = np.asarray(emitted)
        assert [y is not None for y in got[sid]] == emitted.tolist()
        for t in np.flatnonzero(emitted):
            np.testing.assert_array_equal(got[sid][t], np.asarray(outs[t]))
        n_emit += int(emitted.sum())
    m = srv.metrics
    assert m.value("stream.frames") == 2 * n
    assert m.value("stream.device_waits") == m.value("stream.emissions") == n_emit
    assert m.value("stream.frames") - m.value("stream.device_waits") == 2 * n - n_emit


def test_stream_server_caller_may_reuse_frame_buffer(ds):
    """A non-emitting push returns before its step has run on the device,
    so it must not alias the caller's frame: overwriting the buffer right
    after every push leaves the emissions equal to the oracle's."""
    from repro.serve.cnn_engine import StreamServer

    g, _, qm = ds
    srv = StreamServer.from_quantized(qm)
    frames = np.asarray(quantize.quantize_input(
        qm, jax.random.normal(jax.random.PRNGKey(10), (48, 1, 10))))
    ref_outs, ref_em = streaming.sliding_window_reference(
        g, None, frames,
        forward_fn=lambda _, w: quantize.simulate_int8_dag_forward(qm, w))
    buf = np.empty_like(frames[0])
    for t in range(frames.shape[0]):
        buf[...] = frames[t]
        y = srv.push("a", buf)
        buf[...] = -frames[t] - 1  # the caller's next use of its buffer
        if ref_em[t]:
            np.testing.assert_array_equal(y, ref_outs[t])
        else:
            assert y is None


def test_stream_server_reopen_restarts_phase(ds):
    """``close`` drops the host's frame count with the state: after an odd
    number of frames, a reopened stream's first push still does not emit
    (E = 2), and its second does."""
    from repro.serve.cnn_engine import StreamServer

    g, params, _ = ds
    srv = StreamServer.from_graph(g, params, prewarm=False)
    assert srv.executor.splan.emit_stride == 2
    frame = np.ones((1, 10), np.float32)
    for _ in range(3):
        srv.push("s", frame)
    srv.close("s")
    srv.open("s")
    assert srv.push("s", frame) is None
    assert srv.push("s", frame) is not None


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


def test_enable_persistent_cache_writes_entries(tmp_path, monkeypatch):
    from repro.serve.step import enable_persistent_cache

    cache_dir = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
    assert enable_persistent_cache() == str(cache_dir)
    try:
        @jax.jit
        def f(x):
            return jnp.tanh(x) @ x.T

        jax.block_until_ready(f(jnp.ones((64, 64))))
        entries = list(cache_dir.iterdir())
        assert entries, "persistent cache wrote no entries"
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        from jax.experimental.compilation_cache import compilation_cache as _cc

        _cc.reset_cache()  # detach later compiles from the tmp dir


def test_compile_cache_dir_rule(monkeypatch):
    """The environment variable wins; otherwise a fixed in-checkout path."""
    from repro.serve import step

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert step.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(__file__).resolve().parents[1]
    assert step.compile_cache_dir() == str(repo / ".jax_cache")
    assert step.compile_cache_dir() == step.compile_cache_dir()
