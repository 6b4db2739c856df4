"""Post-training int8 quantization (paper §5 quantizes the test net to int8).

Symmetric per-tensor quantization, CMSIS-NN-compatible flavour:
  * weights:      int8, scale = max|w| / 127
  * activations:  int8, scale calibrated from a calibration batch (max |x|)
  * accumulation: int32, requantized to int8 between layers

``simulate_int8_forward`` runs the quantized network in JAX with genuine
int8 storage / int32 accumulation so the C deployment numerics can be
validated bit-for-bit against it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (
    Add,
    AvgPool2d,
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
    refuse_batchnorm,
)
from repro.core import nn


@dataclasses.dataclass
class QuantizedLayer:
    name: str
    w_q: np.ndarray  # int8
    b_q: np.ndarray | None  # int32 (bias in accumulator scale)
    # float (per-tensor) or (C,) float array (per-output-channel: depthwise
    # convs, where each channel owns its own k×k filter and a shared scale
    # would be dominated by the widest channel).
    w_scale: float | np.ndarray
    in_scale: float
    out_scale: float

    @property
    def multiplier(self):
        """The layer's requantization multiplier (accumulator → int8).

        A scalar for per-tensor layers, a ``(C,)`` float array for
        per-channel (depthwise) layers — ``requant_multiplier`` is
        elementwise, so both fall out of the same expression.
        """
        return requant_multiplier(self.in_scale, self.w_scale, self.out_scale)

    @property
    def per_channel(self) -> bool:
        return np.ndim(self.w_scale) > 0


@dataclasses.dataclass
class QuantizedJoin:
    """Join-node (Add/Concat) requantization: one int8→int8 multiplier per
    input, rescaling each input's scale onto the join's output scale."""

    name: str
    in_scales: tuple
    out_scale: float

    @property
    def multipliers(self) -> tuple:
        return tuple(s / self.out_scale for s in self.in_scales)


@dataclasses.dataclass
class QuantizedModel:
    graph: SequentialGraph | DAGGraph
    input_scale: float
    layers: Dict[str, QuantizedLayer]
    joins: Dict[str, QuantizedJoin] = dataclasses.field(default_factory=dict)

    def param_bytes(self) -> int:
        total = 0
        for q in self.layers.values():
            total += q.w_q.size  # int8
            if q.b_q is not None:
                total += q.b_q.size * 4
        return total

    def weight_bytes(self) -> int:
        return sum(q.w_q.size for q in self.layers.values())


def _calibrate_scales(graph: SequentialGraph, params, xs) -> Dict[str, float]:
    """Max-abs output scale for every layer, from a calibration batch."""
    scales: Dict[str, float] = {}
    x = xs
    for layer in graph.layers:
        name = layer.name or layer.kind
        x = nn.apply_layer(layer, params.get(name, {}), x)
        scales[name] = max(float(jnp.max(jnp.abs(x))), 1e-8) / 127.0
    return scales


def _is_depthwise(layer) -> bool:
    """True for layers quantized per-output-channel (depthwise, incl. fused)."""
    inner = layer.conv if isinstance(layer, FusedConvPool) else layer
    return isinstance(inner, DepthwiseConv2d)


def _quantize_layer(
    name: str,
    layer_params,
    in_scale: float,
    out_scale: float,
    per_channel: bool = False,
) -> QuantizedLayer:
    """Quantize one conv/linear layer's parameters — the single definition of
    the weight/bias scale math shared by the sequential and DAG quantizers.

    ``per_channel=True`` (depthwise convs) gives every output channel its own
    symmetric weight scale — ``w_scale`` becomes a ``(C,)`` array and the
    bias/requant math applies channel-wise.
    """
    w = np.asarray(layer_params["w"], np.float32)
    if per_channel:
        flat = np.abs(w.reshape(w.shape[0], -1)).max(axis=1)
        w_scale = np.maximum(flat, 1e-8) / 127.0  # (C,)
        w_q = np.clip(
            np.round(w / w_scale.reshape((-1,) + (1,) * (w.ndim - 1))), -127, 127
        ).astype(np.int8)
    else:
        w_scale = max(float(np.max(np.abs(w))), 1e-8) / 127.0
        w_q = np.clip(np.round(w / w_scale), -127, 127).astype(np.int8)
    b = layer_params.get("b")
    b_q = None
    if b is not None:
        # bias lives in the int32 accumulator scale: in_scale*w_scale
        # (per-channel: each channel's own accumulator scale)
        b_q = np.round(np.asarray(b, np.float32) / (in_scale * w_scale)).astype(
            np.int32
        )
    return QuantizedLayer(
        name=name,
        w_q=w_q,
        b_q=b_q,
        w_scale=w_scale,
        in_scale=in_scale,
        out_scale=out_scale,
    )


def quantize(graph: SequentialGraph, params, calibration_x) -> QuantizedModel:
    """Quantize a (fused) graph's parameters given a calibration batch.

    ``calibration_x``: (N, C, H, W) float batch used for activation ranges.
    """
    refuse_batchnorm(graph, caller="quantize")
    act_scales = _calibrate_scales(graph, params, calibration_x)
    input_scale = max(float(jnp.max(jnp.abs(calibration_x))), 1e-8) / 127.0

    layers: Dict[str, QuantizedLayer] = {}
    in_scale = input_scale
    for layer in graph.layers:
        name = layer.name or layer.kind
        out_scale = act_scales[name]
        if name in params:
            layers[name] = _quantize_layer(
                name, params[name], in_scale, out_scale,
                per_channel=_is_depthwise(layer),
            )
        in_scale = out_scale
    return QuantizedModel(graph=graph, input_scale=input_scale, layers=layers)


# ---------------------------------------------------------------------------
# Requantization — the one definition every int8 backend shares.
#
# The eager simulator below, the compiled int8 arena executors
# (repro.quant.exec), the Pallas q8 kernel (repro.quant.kernel_q8) and the C
# emitter (repro.core.export_c, via REQUANT_C) all requantize through these
# helpers, so the backends cannot drift: float32 rescale by
# in_scale·w_scale/out_scale, round-half-to-even, saturate to [-128, 127].
# ---------------------------------------------------------------------------


def requant_multiplier(in_scale: float, w_scale: float, out_scale: float) -> float:
    """Accumulator-scale → output-scale multiplier for one layer."""
    return in_scale * w_scale / out_scale


def requantize(acc_i32: jax.Array, multiplier) -> jax.Array:
    """int32 accumulator → int8 (f32 rescale, round-half-even, saturate).

    ``multiplier`` may be a Python float (trace-time constant, as in the
    simulator and the Pallas kernel) or a traced f32 scalar (as in the scan
    executor, where it rides in the stacked per-layer params) — both are
    cast to float32 first so the arithmetic is identical.
    """
    m = jnp.asarray(multiplier, jnp.float32)
    return jnp.clip(jnp.round(acc_i32.astype(jnp.float32) * m), -128, 127).astype(jnp.int8)


def requantize_per_channel(acc_i32: jax.Array, multipliers) -> jax.Array:
    """Per-output-channel requantization (depthwise convs).

    ``acc_i32`` is ``(..., C, H, W)``; ``multipliers`` a ``(C,)`` vector of
    f32 scales (one accumulator→int8 multiplier per channel), reshaped to
    broadcast over the spatial dims and fed through the shared scalar
    :func:`requantize` math — same rounding, same saturation.
    """
    m = jnp.asarray(multipliers, jnp.float32).reshape((-1, 1, 1))
    return requantize(acc_i32, m)


# The same math as C (nearbyintf rounds half-to-even under the default
# FE_TONEAREST mode, matching jnp.round above bit-for-bit).
REQUANT_C = """
static int8_t rq(int32_t acc, float m) {
  float v = nearbyintf((float)acc * m);
  if (v > 127.0f) return 127;
  if (v < -128.0f) return -128;
  return (int8_t)v;
}"""


def _requant(acc_i32: jax.Array, in_scale: float, w_scale: float, out_scale: float) -> jax.Array:
    """int32 accumulator → int8 output (float rescale, round-to-nearest)."""
    return requantize(acc_i32, requant_multiplier(in_scale, w_scale, out_scale))


def _requant_conv(acc_i32: jax.Array, q: QuantizedLayer) -> jax.Array:
    """Requantize a conv accumulator with the layer's scalar or per-channel
    multiplier (the simulator-side dispatch)."""
    if q.per_channel:
        return requantize_per_channel(acc_i32, q.multiplier)
    return requantize(acc_i32, q.multiplier)


def int8_avgpool(x_i8: jax.Array, kernel, stride, padding=0) -> jax.Array:
    """Int8 average pooling, CMSIS-style: int32 window **sum**, then one
    requantization whose multiplier folds in the ``1/(kh·kw)`` divisor.

    Zero padding is exact under symmetric quantization (zero point 0), and
    dividing by the full window size matches the float oracle's
    count-include-pad semantics.  The divisor multiplier is formed by f32
    division (``f32(1)/f32(kh·kw)``) — the same single-rounding every other
    int8 backend (exec, Pallas q8, C) uses, so the backends agree bit-for-bit.
    """
    kh, kw = _pair(kernel)
    s = nn.sumpool2d(x_i8.astype(jnp.int32), kernel, stride, padding)
    return requantize(s, np.float32(1.0) / np.float32(kh * kw))


def requantize_join(xs_i8, multipliers) -> jax.Array:
    """Int8 Add semantics shared by every backend: requantize each input onto
    the output scale, sum in int32, saturate to [-128, 127].

    The C emitter mirrors this exactly (per-input ``rq`` then an int32 sum
    and clamp), so max-abs calibrated joins stay bit-identical across the
    simulator, the arena executors and the generated engine.
    """
    acc = None
    for x, m in zip(xs_i8, multipliers):
        r = requantize(x.astype(jnp.int32), m).astype(jnp.int32)
        acc = r if acc is None else acc + r
    return jnp.clip(acc, -128, 127).astype(jnp.int8)


def requantize_concat(xs_i8, multipliers, axis: int) -> jax.Array:
    """Int8 Concat: each input segment requantized onto the join scale."""
    parts = [requantize(x.astype(jnp.int32), m) for x, m in zip(xs_i8, multipliers)]
    return jnp.concatenate(parts, axis=axis)


def quantize_input(qm: QuantizedModel, x: jax.Array) -> jax.Array:
    return jnp.clip(jnp.round(x / qm.input_scale), -128, 127).astype(jnp.int8)


def quantize_dag(graph: DAGGraph, params, calibration_x) -> QuantizedModel:
    """Quantize a (fused) DAG's parameters given a calibration batch.

    Per-node symmetric scales, calibrated on the float activations in one
    topological sweep.  Scale-preserving nodes (ReLU/Flatten/MaxPool) pass
    their input's scale through — their int8 output really does carry the
    producer's scale, so calibrating them separately would skew downstream
    multipliers.  Conv/linear nodes get the paper's accumulator-scale bias +
    requant multiplier; joins (Add/Concat) get one int8→int8 multiplier per
    input (:class:`QuantizedJoin`).
    """
    refuse_batchnorm(graph, caller="quantize_dag")
    input_scale = max(float(jnp.max(jnp.abs(calibration_x))), 1e-8) / 127.0
    scales: Dict[str, float] = {}
    vals: Dict[str, jax.Array] = {}
    layers: Dict[str, QuantizedLayer] = {}
    joins: Dict[str, QuantizedJoin] = {}

    for node in graph.nodes:
        name = node.name
        if isinstance(node.layer, Input):
            vals[name] = calibration_x
            scales[name] = input_scale
            continue
        xs = [vals[src] for src in node.inputs]
        val = nn.apply_node(node.layer, params.get(name, {}), xs)
        vals[name] = val
        if isinstance(node.layer, (Add, Concat)):
            out_scale = max(float(jnp.max(jnp.abs(val))), 1e-8) / 127.0
            joins[name] = QuantizedJoin(
                name=name,
                in_scales=tuple(scales[src] for src in node.inputs),
                out_scale=out_scale,
            )
            scales[name] = out_scale
            continue
        if name not in params:
            scales[name] = scales[node.inputs[0]]  # scale-preserving node
            continue
        in_scale = scales[node.inputs[0]]
        out_scale = max(float(jnp.max(jnp.abs(val))), 1e-8) / 127.0
        layers[name] = _quantize_layer(
            name, params[name], in_scale, out_scale,
            per_channel=_is_depthwise(node.layer),
        )
        scales[name] = out_scale
    return QuantizedModel(
        graph=graph, input_scale=input_scale, layers=layers, joins=joins
    )


def simulate_int8_forward(qm: QuantizedModel, x_q: jax.Array) -> jax.Array:
    """Run the int8 network (int8 tensors, int32 accumulation) in JAX.

    Returns the final layer's int8 output.  Matches the generated C engine.
    One chain walk over the shared per-node semantics
    (:func:`_simulate_int8_node`), so the sequential and DAG simulators
    cannot drift.
    """
    x = x_q
    for layer in qm.graph.layers:
        if isinstance(layer, Input):
            continue
        x = _simulate_int8_node(qm, layer, layer.name or layer.kind, [x])
    return x


def _simulate_int8_node(qm: QuantizedModel, layer, name: str, xs) -> jax.Array:
    """One node of the int8 DAG simulation (int8 tensors, int32 accumulate)."""
    x = xs[0]
    if isinstance(layer, ReLU):
        return jnp.maximum(x, 0)
    if isinstance(layer, Flatten):
        return x.reshape(-1) if x.ndim == 3 else x.reshape(x.shape[0], -1)
    if isinstance(layer, MaxPool2d):
        # padding pads with -128 (the int8 minimum) — the identity of max —
        # matching the float oracle's -inf padding and the C engine.
        return nn.maxpool2d(x, layer.kernel_size, layer.stride, layer.padding)
    if isinstance(layer, AvgPool2d):
        return int8_avgpool(x, layer.kernel_size, layer.stride, layer.padding)
    if isinstance(layer, (Add, Concat)):
        j = qm.joins[name]
        if isinstance(layer, Add):
            return requantize_join(xs, j.multipliers)
        return requantize_concat(xs, j.multipliers, axis=layer.axis)
    q = qm.layers[name]
    if isinstance(layer, (Conv2d, DepthwiseConv2d, FusedConvPool)):
        conv = layer.conv if isinstance(layer, FusedConvPool) else layer
        acc = jax.lax.conv_general_dilated(
            x.astype(jnp.int32)[None] if x.ndim == 3 else x.astype(jnp.int32),
            jnp.asarray(q.w_q, jnp.int32),
            window_strides=conv.stride,
            padding=[(p, p) for p in conv.padding],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=(
                conv.channels if isinstance(conv, DepthwiseConv2d) else 1
            ),
        )
        if x.ndim == 3:
            acc = acc[0]
        if q.b_q is not None:
            bias = jnp.asarray(q.b_q, jnp.int32)
            acc = acc + (bias[:, None, None] if acc.ndim == 3 else bias[None, :, None, None])
        if isinstance(layer, FusedConvPool):
            if layer.activation == "relu":
                acc = jnp.maximum(acc, 0)
            if layer.pool == "avg":
                # Fused average: window SUM in the int32 accumulator domain,
                # then one requantization with 1/(pkh·pkw) folded into the
                # multiplier (f32 division — the shared canonical order).
                pkh, pkw = layer.pool_kernel
                s = nn.sumpool2d(acc, layer.pool_kernel, layer.pool_stride)
                m = np.asarray(q.multiplier, np.float32) / np.float32(pkh * pkw)
                if q.per_channel:
                    return requantize_per_channel(s, m)
                return requantize(s, m)
            y = _requant_conv(acc, q)
            return nn.maxpool2d(y, layer.pool_kernel, layer.pool_stride)
        return _requant_conv(acc, q)
    if isinstance(layer, (Linear, FusedLinear)):
        acc = x.astype(jnp.int32) @ jnp.asarray(q.w_q, jnp.int32).T
        if q.b_q is not None:
            acc = acc + jnp.asarray(q.b_q, jnp.int32)
        if isinstance(layer, FusedLinear) and layer.activation == "relu":
            acc = jnp.maximum(acc, 0)
        return _requant(acc, q.in_scale, q.w_scale, q.out_scale)
    raise TypeError(f"unsupported layer for int8 simulation: {layer!r}")


def simulate_int8_dag_forward(qm: QuantizedModel, x_q: jax.Array) -> jax.Array:
    """Run the int8 DAG (int8 tensors, int32 accumulation) eagerly in JAX.

    The independent slow oracle for the int8 DAG executors and the generated
    C engine — matches both bit-for-bit.
    """
    g = qm.graph
    if not isinstance(g, DAGGraph):
        raise TypeError("simulate_int8_dag_forward expects a DAG-quantized model")
    vals: Dict[str, jax.Array] = {}
    for node in g.nodes:
        if isinstance(node.layer, Input):
            vals[node.name] = x_q
            continue
        vals[node.name] = _simulate_int8_node(
            qm, node.layer, node.name, [vals[src] for src in node.inputs]
        )
    return vals[g.output]
