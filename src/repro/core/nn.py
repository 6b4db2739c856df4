"""Pure-jnp numerics for the paper's layer set (the functional oracle).

These are the reference semantics for the microcontroller-side networks
(LeNet-5, CIFAR test net): PyTorch-compatible Conv2d/MaxPool2d/Linear in CHW
layout.  The Pallas kernel in ``repro.kernels.conv_pool`` and the generated C
code are both validated against these functions.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (
    Add,
    AvgPool2d,
    BatchNorm2d,
    Concat,
    Conv2d,
    DAGGraph,
    DepthwiseConv2d,
    Flatten,
    FusedConvPool,
    FusedLinear,
    Input,
    Linear,
    MaxPool2d,
    ReLU,
    SequentialGraph,
    _pair,
)

Params = Dict[str, Dict[str, jax.Array]]


def conv2d(x: jax.Array, w: jax.Array, b, stride=1, padding=0) -> jax.Array:
    """x: (C,H,W) or (N,C,H,W); w: (O,I,kh,kw); b: (O,) or None.

    ``stride``/``padding`` are per-axis ``(h, w)`` pairs; ints broadcast.
    """
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(sh, sw),
        padding=[(ph, ph), (pw, pw)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    if b is not None:
        out = out + b[None, :, None, None]
    return out[0] if squeeze else out


def depthwise_conv2d(x: jax.Array, w: jax.Array, b, stride=1, padding=0) -> jax.Array:
    """x: (C,H,W) or (N,C,H,W); w: (C,1,kh,kw) [grouped OIHW]; b: (C,) or None."""
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(sh, sw),
        padding=[(ph, ph), (pw, pw)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=w.shape[0],
    )
    if b is not None:
        out = out + b[None, :, None, None]
    return out[0] if squeeze else out


def maxpool2d(x: jax.Array, kernel, stride, padding=0) -> jax.Array:
    """x: (C,H,W) or (N,C,H,W).  All geometry is per-axis (ints broadcast).

    ``padding`` pads with the dtype minimum (``-inf`` float, ``-128`` int8)
    before the window reduction — the identity of ``max`` — so padded
    windows agree with :meth:`MaxPool2d.out_shape` and the emitted C
    engine (which skips out-of-bounds taps against a dtype-min running
    max).  ``reduce_window`` realizes exactly that: padded positions take
    the init value.
    """
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    if jnp.issubdtype(x.dtype, jnp.floating):
        init = -jnp.inf
    else:
        init = np.asarray(jnp.iinfo(x.dtype).min, dtype=x.dtype)
    out = jax.lax.reduce_window(
        x,
        init,
        jax.lax.max,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, sh, sw),
        padding=((0, 0), (0, 0), (ph, ph), (pw, pw)),
    )
    return out[0] if squeeze else out


def sumpool2d(x: jax.Array, kernel, stride, padding=0) -> jax.Array:
    """Window **sum** over zero padding — the shared reduction under both
    the float :func:`avgpool2d` and the int8 accumulator-domain average
    (``quantize.int8_avgpool``, which calls this on the int32-cast input).
    """
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    squeeze = x.ndim == 3
    if squeeze:
        x = x[None]
    out = jax.lax.reduce_window(
        x,
        np.zeros((), x.dtype)[()],
        jax.lax.add,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, sh, sw),
        padding=((0, 0), (0, 0), (ph, ph), (pw, pw)),
    )
    return out[0] if squeeze else out


def avgpool2d(x: jax.Array, kernel, stride, padding=0) -> jax.Array:
    """Average pooling, PyTorch ``count_include_pad=True`` semantics.

    The window is zero-padded and **every** window divides by the full
    ``kh·kw`` — padded positions count toward the divisor (PyTorch's
    default; pinned against it in the tests).  Float only: the int8
    backends go through ``quantize.int8_avgpool`` (int32 window sum, one
    requantization with the divisor folded into the multiplier).
    """
    kh, kw = _pair(kernel)
    return sumpool2d(x, kernel, stride, padding) / (kh * kw)


def batchnorm2d(x: jax.Array, p, eps: float) -> jax.Array:
    """Eval-mode batch norm over the channel axis of (C,H,W) or (N,C,H,W):
    ``(x - mean) / sqrt(var + eps) * gamma + beta``, per channel."""
    gamma, beta, mean, var = (p[k][:, None, None] for k in ("gamma", "beta", "mean", "var"))
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _conv_like(conv, p, x: jax.Array) -> jax.Array:
    """Dispatch the conv of a (fused) conv layer: dense or depthwise."""
    if isinstance(conv, DepthwiseConv2d):
        return depthwise_conv2d(x, p["w"], p.get("b"), conv.stride, conv.padding)
    return conv2d(x, p["w"], p.get("b"), conv.stride, conv.padding)


def linear(x: jax.Array, w: jax.Array, b) -> jax.Array:
    """x: (..., in); w: (out, in) [PyTorch layout]; b: (out,) or None."""
    out = x @ w.T
    if b is not None:
        out = out + b
    return out


_ACT = {"relu": jax.nn.relu, "none": lambda x: x}


def init_params(graph: SequentialGraph, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Kaiming-uniform init matching PyTorch defaults (fan_in based)."""
    params: Params = {}
    for layer in graph.layers:
        name = layer.name or layer.kind
        inner = layer
        if isinstance(layer, FusedConvPool):
            inner = layer.conv
        elif isinstance(layer, FusedLinear):
            inner = layer.linear
        if isinstance(inner, Conv2d):
            rng, k1, k2 = jax.random.split(rng, 3)
            kh, kw = inner.kernel_size
            fan_in = inner.in_channels * kh * kw
            bound = 1.0 / np.sqrt(fan_in)
            w = jax.random.uniform(
                k1,
                (inner.out_channels, inner.in_channels, kh, kw),
                dtype,
                -bound,
                bound,
            )
            b = jax.random.uniform(k2, (inner.out_channels,), dtype, -bound, bound) if inner.bias else None
            params[name] = {"w": w} | ({"b": b} if b is not None else {})
        elif isinstance(inner, DepthwiseConv2d):
            rng, k1, k2 = jax.random.split(rng, 3)
            kh, kw = inner.kernel_size
            # PyTorch grouped-conv fan_in: in_channels/groups * kh·kw = kh·kw.
            bound = 1.0 / np.sqrt(kh * kw)
            w = jax.random.uniform(
                k1,
                (inner.channels, 1, kh, kw),
                dtype,
                -bound,
                bound,
            )
            b = jax.random.uniform(k2, (inner.channels,), dtype, -bound, bound) if inner.bias else None
            params[name] = {"w": w} | ({"b": b} if b is not None else {})
        elif isinstance(inner, BatchNorm2d):
            # PyTorch's defaults: identity statistics and affine.
            ones, zeros = jnp.ones((inner.channels,), dtype), jnp.zeros((inner.channels,), dtype)
            params[name] = {"gamma": ones, "beta": zeros, "mean": zeros, "var": ones}
        elif isinstance(inner, Linear):
            rng, k1, k2 = jax.random.split(rng, 3)
            bound = 1.0 / np.sqrt(inner.in_features)
            w = jax.random.uniform(k1, (inner.out_features, inner.in_features), dtype, -bound, bound)
            b = jax.random.uniform(k2, (inner.out_features,), dtype, -bound, bound) if inner.bias else None
            params[name] = {"w": w} | ({"b": b} if b is not None else {})
    return params


def apply_layer(layer, p, x: jax.Array) -> jax.Array:
    """Apply one layer functionally.  ``p`` is the layer's param dict."""
    if isinstance(layer, Input):
        return x
    if isinstance(layer, Conv2d):
        return conv2d(x, p["w"], p.get("b"), layer.stride, layer.padding)
    if isinstance(layer, DepthwiseConv2d):
        return depthwise_conv2d(x, p["w"], p.get("b"), layer.stride, layer.padding)
    if isinstance(layer, BatchNorm2d):
        return batchnorm2d(x, p, layer.eps)
    if isinstance(layer, ReLU):
        return jax.nn.relu(x)
    if isinstance(layer, MaxPool2d):
        return maxpool2d(x, layer.kernel_size, layer.stride, layer.padding)
    if isinstance(layer, AvgPool2d):
        return avgpool2d(x, layer.kernel_size, layer.stride, layer.padding)
    if isinstance(layer, Flatten):
        return x.reshape(x.shape[:-3] + (-1,)) if x.ndim > 3 else x.reshape(-1)
    if isinstance(layer, Linear):
        return linear(x, p["w"], p.get("b"))
    if isinstance(layer, FusedConvPool):
        y = _conv_like(layer.conv, p, x)
        y = _ACT[layer.activation](y)
        if layer.pool == "avg":
            return avgpool2d(y, layer.pool_kernel, layer.pool_stride)
        return maxpool2d(y, layer.pool_kernel, layer.pool_stride)
    if isinstance(layer, FusedLinear):
        return _ACT[layer.activation](linear(x, p["w"], p.get("b")))
    raise TypeError(f"unknown layer {layer!r}")


def apply_node(layer, p, xs) -> jax.Array:
    """Apply one layer to its input list (DAG form).

    Join nodes (:class:`Add`, :class:`Concat`) consume all inputs;
    single-input layers delegate to :func:`apply_layer`.
    """
    if isinstance(layer, Add):
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out
    if isinstance(layer, Concat):
        return jnp.concatenate(list(xs), axis=layer.axis)
    if len(xs) != 1:
        raise ValueError(f"{layer.name or layer.kind}: expected one input, got {len(xs)}")
    return apply_layer(layer, p, xs[0])


def forward(graph: SequentialGraph, params: Params, x: jax.Array) -> jax.Array:
    """Functional forward pass (the oracle the arena executor is tested on)."""
    for layer in graph.layers:
        name = layer.name or layer.kind
        x = apply_layer(layer, params.get(name, {}), x)
    return x


def forward_dag(graph: DAGGraph, params: Params, x: jax.Array) -> jax.Array:
    """Functional DAG forward pass (the float oracle for the DAG executors)."""
    vals: Dict[str, jax.Array] = {}
    for node in graph.nodes:
        if isinstance(node.layer, Input):
            vals[node.name] = x
            continue
        vals[node.name] = apply_node(
            node.layer,
            params.get(node.name, {}),
            [vals[src] for src in node.inputs],
        )
    return vals[graph.output]
