"""Eval-mode BatchNorm2d: the float oracle, its fold into the conv before
it (``fusion.fold_batchnorm``), and the refusal of an unfolded norm by every
deployment path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import export_c, fusion, nn, pingpong, planner, quantize, schedule
from repro.core.graph import (
    Add,
    BatchNorm2d,
    Conv2d,
    DAGGraph,
    DepthwiseConv2d,
    Flatten,
    Input,
    Linear,
    MaxPool2d,
    Node,
    ReLU,
    SequentialGraph,
)
from repro.quant.exec import make_int8_executor


def _stats(c, key):
    ks = jax.random.split(key, 4)
    return {"gamma": jax.random.uniform(ks[0], (c,), minval=0.5, maxval=1.5),
            "beta": jax.random.uniform(ks[1], (c,), minval=-0.5, maxval=0.5),
            "mean": jax.random.uniform(ks[2], (c,), minval=-0.5, maxval=0.5),
            "var": jax.random.uniform(ks[3], (c,), minval=0.5, maxval=2.0)}


def _params(g, seed=0):
    params = nn.init_params(g, jax.random.PRNGKey(seed))
    for i, layer in enumerate(g.layers):
        if isinstance(layer, BatchNorm2d):
            params[layer.name] = _stats(layer.channels, jax.random.PRNGKey(100 + i))
    return params


def _conv_bn():
    return DAGGraph([
        Node(Input(shape=(4, 8, 8), name="input")),
        Node(Conv2d(4, 6, kernel_size=3, padding=1, name="conv"), ("input",)),
        Node(BatchNorm2d(6, eps=1e-3, name="bn"), ("conv",)),
        Node(ReLU(name="relu"), ("bn",)),
        Node(MaxPool2d(2, 2, name="pool"), ("relu",)),
    ])


def _dw_bn(bias):
    return DAGGraph([
        Node(Input(shape=(4, 8, 8), name="input")),
        Node(DepthwiseConv2d(4, kernel_size=3, stride=2, padding=1, bias=bias, name="dw"),
             ("input",)),
        Node(BatchNorm2d(4, name="bn"), ("dw",)),
    ])


def _conv_bn_add():
    return DAGGraph([
        Node(Input(shape=(4, 8, 8), name="input")),
        Node(Conv2d(4, 4, kernel_size=3, padding=1, name="a"), ("input",)),
        Node(BatchNorm2d(4, name="a_bn"), ("a",)),
        Node(ReLU(name="a_relu"), ("a_bn",)),
        Node(Conv2d(4, 4, kernel_size=3, padding=1, name="b"), ("a_relu",)),
        Node(BatchNorm2d(4, name="b_bn"), ("b",)),
        Node(Add(name="add"), ("input", "b_bn")),
        Node(ReLU(name="add_relu"), ("add",)),
        Node(Flatten(name="flatten"), ("add_relu",)),
        Node(Linear(256, 5, name="fc"), ("flatten",)),
    ])


def test_oracle_is_eval_mode_batchnorm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 5))
    p = _stats(3, jax.random.PRNGKey(1))
    y = nn.apply_layer(BatchNorm2d(3, eps=1e-3), p, x)
    c = lambda v: np.asarray(v)[None, :, None, None]
    want = (np.asarray(x) - c(p["mean"])) / np.sqrt(c(p["var"]) + 1e-3) * c(p["gamma"]) + c(p["beta"])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6, atol=1e-6)
    init = nn.init_params(SequentialGraph([Input(shape=(3, 4, 5)), BatchNorm2d(3, name="bn")]),
                          jax.random.PRNGKey(0))["bn"]
    # PyTorch's init: identity statistics, so only eps (1e-5) scales x.
    np.testing.assert_allclose(np.asarray(nn.apply_layer(BatchNorm2d(3), init, x)),
                               np.asarray(x) / np.sqrt(1 + 1e-5), rtol=1e-6, atol=1e-7)
    assert BatchNorm2d(3).param_count() == 12
    with pytest.raises(ValueError, match="channels"):
        BatchNorm2d(3).out_shape((4, 2, 2))


@pytest.mark.parametrize("build", [_conv_bn, lambda: _dw_bn(True), lambda: _dw_bn(False),
                                   _conv_bn_add], ids=["conv", "dwconv", "dwconv_nobias", "add"])
def test_fold_matches_unfolded_oracle(build):
    """Folding changes only f32 rounding: the scale is multiplied into the
    weights before the sums instead of into the sums (tolerance 1e-5 of the
    output's size; a norm left out moves it by 1e-1 or more)."""
    g = build()
    params = _params(g)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 4, 8, 8))
    folded, fparams = fusion.fold_batchnorm(g, params)
    assert not any(isinstance(layer, BatchNorm2d) for layer in folded.layers)
    assert set(fparams) == {n for n in params if not n.endswith("bn")}
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(nn.forward_dag(g, params, x))
        y = np.asarray(nn.forward_dag(folded, fparams, x))
    np.testing.assert_allclose(y, ref, rtol=0, atol=1e-5 * np.abs(ref).max())
    # Every folded conv carries a bias, and the readers read the conv.
    for node in folded.nodes:
        if isinstance(node.layer, (Conv2d, DepthwiseConv2d)):
            assert node.layer.bias
            assert fparams[node.name]["b"].shape == fparams[node.name]["w"].shape[:1]
        assert not any(src.endswith("bn") for src in node.inputs)


def test_fold_without_norms_is_identity():
    g, params = fusion.fold_batchnorm(_conv_bn(), _params(_conv_bn()))
    again, same = fusion.fold_batchnorm(g, params)
    assert again == g and same == params


def test_fold_refuses_a_norm_after_a_pool():
    g = DAGGraph([
        Node(Input(shape=(4, 8, 8), name="input")),
        Node(Conv2d(4, 4, kernel_size=3, padding=1, name="conv"), ("input",)),
        Node(MaxPool2d(2, 2, name="pool"), ("conv",)),
        Node(BatchNorm2d(4, name="bn"), ("pool",)),
    ])
    with pytest.raises(ValueError, match="'bn' reads 'pool', a MaxPool2d"):
        fusion.fold_batchnorm(g, _params(g))


def test_fold_refuses_a_conv_with_a_second_reader():
    g = DAGGraph([
        Node(Input(shape=(4, 8, 8), name="input")),
        Node(Conv2d(4, 4, kernel_size=3, padding=1, name="conv"), ("input",)),
        Node(BatchNorm2d(4, name="bn"), ("conv",)),
        Node(Add(name="add"), ("conv", "bn")),
    ])
    with pytest.raises(ValueError, match=r"'conv' is read by \['add'\] besides 'bn'"):
        fusion.fold_batchnorm(g, _params(g))


def test_unfolded_norm_is_refused_by_planner_executors_quantizer_and_c():
    """Planning, executing, quantizing or emitting C for a graph that still
    holds a BatchNorm2d raises and names the node."""
    g = _conv_bn_add()
    params = _params(g)
    folded, fparams = fusion.fold_batchnorm(g, params)
    plan = schedule.plan_dag(folded)
    x = jnp.zeros((1, 4, 8, 8))
    calls = (
        lambda: schedule.plan_dag(g),
        lambda: pingpong.make_dag_executor(g, plan),
        lambda: pingpong.run_dag_with_arena(g, plan, params, x[0]),
        lambda: quantize.quantize_dag(g, params, x),
        lambda: export_c.generate_c_dag(g, plan, params),
    )
    for call in calls:
        with pytest.raises(ValueError, match="node 'a_bn' is an unfolded BatchNorm2d"):
            call()
    qm = quantize.quantize_dag(folded, fparams, jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 8)))
    plan_q = schedule.plan_dag(folded, io_dtype_bytes=1)
    bad = quantize.QuantizedModel(graph=g, input_scale=qm.input_scale, layers=qm.layers,
                                  joins=qm.joins)
    for call in (lambda: make_int8_executor(bad, plan_q),
                 lambda: export_c.generate_c_int8_dag(bad, plan_q)):
        with pytest.raises(ValueError, match="node 'a_bn' is an unfolded BatchNorm2d"):
            call()
    # The sequential entry points refuse it too.
    seq = _conv_bn().to_sequential()
    for call in (lambda: planner.plan_pingpong(seq), lambda: fusion.fuse(seq),
                 lambda: pingpong.make_scan_executor(seq, plan)):
        with pytest.raises(ValueError, match="node 'bn' is an unfolded BatchNorm2d"):
            call()
