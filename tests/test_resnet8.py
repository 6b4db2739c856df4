"""ResNet-8 (MLPerf Tiny image classification) as published, BatchNorm
included, through the normal path: ``fold_batchnorm`` → ``fuse_dag`` →
``plan_dag`` → the f32 and int8 DAG executors → ``CNNEngine`` → the int8 C
engine under gcc.  Published widths, a batch of 4-6, on the CPU.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import export_c, fusion, nn, quantize, schedule, segments
from repro.core.graph import BatchNorm2d, resnet8
from repro.core.pingpong import make_dag_executor
from repro.quant.exec import make_int8_executor
from repro.serve.cnn_engine import CNNEngine, CoalescePolicy

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import netspec  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import seeded  # noqa: E402

CFG = netspec.load("resnet8_cifar_int8")
JOINS = ("s1add", "s2add", "s3add")


def _bn_stats(params, graph, key):
    """Trained-looking statistics in place of the identity init."""
    out = dict(params)
    for layer in graph.layers:
        if isinstance(layer, BatchNorm2d):
            key, *ks = jax.random.split(key, 5)
            c = (layer.channels,)
            out[layer.name] = {
                "gamma": jax.random.uniform(ks[0], c, minval=0.5, maxval=1.5),
                "beta": jax.random.uniform(ks[1], c, minval=-0.3, maxval=0.3),
                "mean": jax.random.uniform(ks[2], c, minval=-0.5, maxval=0.5),
                "var": jax.random.uniform(ks[3], c, minval=0.5, maxval=2.0),
            }
    return out


@pytest.fixture(scope="module")
def float_net():
    g = resnet8()
    params = _bn_stats(nn.init_params(g, jax.random.PRNGKey(0)), g, jax.random.PRNGKey(1))
    folded, fparams = fusion.fold_batchnorm(g, params)
    fused = fusion.fuse_dag(folded)
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 3, 32, 32))
    return g, params, folded, fused, fusion.rename_params(fused, fparams), x


@pytest.fixture(scope="module")
def int8_net(float_net):
    _, _, folded, fused, fparams, x = float_net
    qm = quantize.quantize_dag(fused, fparams, x)
    plan = schedule.plan_dag(folded, io_dtype_bytes=1)
    fn, qparams = make_int8_executor(qm, plan)
    x_q = quantize.quantize_input(qm, x[:4])
    return qm, plan, fn, qparams, x_q


def test_folded_float_executor_matches_unfolded_oracle(float_net):
    """(a) With BN statistics away from the identity, the folded, fused and
    planned f32 executor gives the unfolded oracle's logits.  Tolerance: the
    fold multiplies the scale into the weights before the sums where the
    oracle scales the sums, which reorders f32 rounding in each of the nine
    normalized convs; that moves the logits by about 2e-7 of the largest, so
    1e-4 of the largest logit holds with room and still catches an ``eps``
    read as PyTorch's 1e-5 (7e-4) or one norm's mean left out (0.8)."""
    g, params, folded, fused, fparams, x = float_net
    plan = schedule.plan_dag(folded)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(make_dag_executor(fused, plan)(fparams, x))
        ref = np.asarray(nn.forward_dag(g, params, x))
    assert y.shape == (6, 10)
    np.testing.assert_allclose(y, ref, rtol=0, atol=1e-4 * np.abs(ref).max())


def test_folded_graph_is_the_benchmark_configuration(float_net):
    """(b) ``resnet8()`` folded is node for node the graph the benchmark
    builds from ``resnet8_cifar_int8.json``, with its pinned counts."""
    g, _, folded, _, _, _ = float_net
    assert folded == program.graph(CFG)
    shapes = folded.shapes()
    macs = sum(n.layer.macs(shapes[n.inputs[0]]) for n in folded.nodes if n.inputs)
    assert macs == CFG["expect"]["macs_per_image"] == 12_501_632
    assert folded.param_count() == CFG["expect"]["params"] == 77_706
    assert g.param_count() == 78_666
    plan = schedule.plan_dag(folded, io_dtype_bytes=1)
    assert plan.arena_elems == CFG["expect"]["arena_bytes_int8"] == 49_152
    _, _, segs = segments.segments_for_plan(fusion.fuse_dag(folded), plan)
    assert len(segs) == CFG["expect"]["segments"] == 14


def test_int8_executor_bit_exact_vs_simulator(int8_net):
    """(c) Calibrated int8: the executor equals the eager simulator."""
    qm, _, fn, qparams, x_q = int8_net
    y = np.asarray(fn(qparams, x_q))
    sim = np.asarray(jax.vmap(lambda v: quantize.simulate_int8_dag_forward(qm, v))(x_q))
    np.testing.assert_array_equal(y, sim)
    assert np.abs(y.astype(int)).max() > 8  # not collapsed to zero


@pytest.mark.parametrize("seed", [2**31 + 17, 5])
def test_int8_executor_bit_exact_vs_bench_reference(seed):
    """(c) On the benchmark's seeded weights the executor equals the plain
    int8 reference, which imports nothing of the program."""
    weights = seeded.draw(CFG, seed)
    built = program.build(CFG, weights)
    fn, qparams = make_int8_executor(built.qm, built.plan)
    rng = np.random.default_rng(seed)
    x = rng.integers(-128, 128, (4, *CFG["input"]), dtype=np.int8)
    y = np.asarray(fn(qparams, jnp.asarray(x)))
    ref = np.asarray(reference.forward(CFG, weights, x))
    np.testing.assert_array_equal(y, ref)
    assert len(np.unique(y)) > 4


def test_int8_c_engine_matches_executor(int8_net):
    """(d) The emitted int8 C engine, compiled by gcc, equals the executor."""
    qm, plan, fn, qparams, x_q = int8_net
    y = np.asarray(fn(qparams, x_q[:2]))
    src = export_c.generate_c_int8_dag(qm, plan, with_main=True)
    with tempfile.TemporaryDirectory() as td:
        c_path, bin_path = os.path.join(td, "net.c"), os.path.join(td, "net")
        Path(c_path).write_text(src)
        subprocess.run(["gcc", "-O2", "-std=c99", c_path, "-o", bin_path, "-lm"],
                       check=True, capture_output=True)
        for i in range(2):
            out = subprocess.run([bin_path], input=np.asarray(x_q[i]).tobytes(),
                                 capture_output=True, check=True).stdout
            np.testing.assert_array_equal(np.frombuffer(out, np.int8), y[i])


def test_engine_returns_executor_logits(int8_net):
    """(e) ``CNNEngine`` serves single requests with the executor's logits."""
    qm, plan, fn, qparams, x_q = int8_net
    y = np.asarray(fn(qparams, x_q))
    eng = CNNEngine.from_quantized(qm, plan, buckets=(1, 2),
                                   policy=CoalescePolicy(max_batch=2, max_wait_s=0.001))
    with eng:
        reqs = [eng.submit(np.asarray(x_q[i])) for i in range(len(x_q))]
        got = np.stack([r.result(timeout=120.0) for r in reqs])
    np.testing.assert_array_equal(got, y)


def test_compiled_executor_names_each_join(int8_net):
    """The compiled int8 executor runs each join under
    ``seg<i>.single/join.<node>``, and ``segment_stats`` counts them."""
    qm, plan, fn, qparams, _ = int8_net
    _, _, segs = segments.segments_for_plan(qm.graph, plan)
    assert segments.segment_stats(segs)["join_steps"] == 3
    text = fn.lower(qparams, jax.ShapeDtypeStruct((2, 3, 32, 32), jnp.int8)).compile().as_text()
    at = {seg.branches[0][0]: i for i, seg in enumerate(segs)}
    for join in JOINS:
        assert segs[at[join]].join
        assert f"seg{at[join]}.single/join.{join}/" in text, join
    assert sum(seg.join for seg in segs) == 3
