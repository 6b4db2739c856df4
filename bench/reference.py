"""Plain int8 reference forward pass of a configuration.

Written from the configuration's layer list (``netspec``) and the int8
rules it states; imports nothing of the program and takes only the weights
that ``seeded.draw`` made. Every rule:

* int8 activations and weights, int32 accumulation, int32 bias added to the
  accumulator;
* a ReLU is applied to the accumulator;
* requantization: ``round_half_even(f32(acc) * m)`` saturated to
  [-128, 127], with ``m`` the layer's f32 multiplier (per channel for a
  depthwise conv);
* a fused average pool sums the ReLU'd accumulators over each window and
  requantizes once with ``m / f32(kh * kw)``; a standalone one sums the int8
  values and requantizes with ``f32(1) / f32(kh * kw)``;
* a max pool takes the largest int8 value of each window, padding with -128;
* an ``add`` requantizes each int8 input with its own multiplier
  (saturated), sums those in int32, saturates the sum to [-128, 127] and
  takes its ReLU on that;
* the classifier flattens the map and gives int8 logits.

Each layer reads its inputs by name (``netspec``); a name holds the layer's
int8 output after its ReLU.

``bits=4`` is the control: the same pass with weights, the input and every
activation, join and pool output rounded to the int4 grid (steps of 16 on
the int8 scale), the nearest precision below the configuration's int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from netspec import walk


def _requant(acc, m):
    y = jnp.round(acc.astype(jnp.float32) * m)
    return jnp.clip(y, -128, 127).astype(jnp.int32)


def _grid(bits: int):
    """Rounds an int8-scale value to the ``bits``-bit grid (identity at 8)."""
    if bits == 8:
        return lambda v: v
    step = 2 ** (8 - bits)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return lambda v: jnp.clip(jnp.round(v / step), lo, hi).astype(jnp.int32) * step


def _window_sum(x, kernel, stride):
    """Sum over non-overlapping pooling windows (stride == kernel here)."""
    (kh, kw), (sh, sw) = kernel, stride
    n, c, h, w = x.shape
    oh, ow = (h - kh) // sh + 1, (w - kw) // sw + 1
    if (sh, sw) == (kh, kw):
        x = x[:, :, : oh * kh, : ow * kw].reshape(n, c, oh, kh, ow, kw)
        return x.sum(axis=(3, 5))
    return jax.lax.reduce_window(x, 0, jax.lax.add, (1, 1, kh, kw), (1, 1, sh, sw), "VALID")


def _max_pool(x, layer):
    (kh, kw), (sh, sw) = layer["kernel"], layer["stride"]
    ph, pw = layer.get("padding", (0, 0))
    return jax.lax.reduce_window(x, jnp.int32(-128), jax.lax.max, (1, 1, kh, kw),
                                 (1, 1, sh, sw), ((0, 0), (0, 0), (ph, ph), (pw, pw)))


def forward(cfg: dict, weights: dict, x, bits: int = 8):
    """Int8 logits ``(N, classes)`` for int8 images ``x`` ``(N, C, H, W)``."""
    layers = walk(cfg)
    q = _grid(bits)
    vals = {"input": q(jnp.asarray(x).astype(jnp.int32))}
    for i, layer in enumerate(layers):
        op, name = layer["op"], layer["name"]
        if layer.get("fused"):
            continue  # taken with the conv before it
        h = vals[layer["inputs"][0]]
        if op == "avgpool":
            kh, kw = layer["kernel"]
            s = _window_sum(h, layer["kernel"], layer["stride"])
            vals[name] = q(_requant(s, jnp.float32(1) / jnp.float32(kh * kw)))
            continue
        if op == "maxpool":
            vals[name] = q(_max_pool(h, layer))
            continue
        p = weights[name]
        m = p["m"].astype(jnp.float32)
        if op == "add":
            acc = sum(_requant(vals[src], m[k]) for k, src in enumerate(layer["inputs"]))
            acc = jnp.clip(acc, -128, 127)
            vals[name] = q(jnp.maximum(acc, 0) if layer.get("relu") else acc)
            continue
        w = q(p["w"].astype(jnp.int32))
        if op == "fc":
            acc = h.reshape(h.shape[0], -1) @ w.T + p["b"]
            vals[name] = q(_requant(acc, m))
            continue
        depthwise = op == "dwconv"
        acc = jax.lax.conv_general_dilated(
            h, w, window_strides=tuple(layer["stride"]),
            padding=[(p_, p_) for p_ in layer["padding"]],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=layer["in_shape"][0] if depthwise else 1,
            preferred_element_type=jnp.int32,
        ) + p["b"][None, :, None, None]
        if layer.get("relu"):
            acc = jnp.maximum(acc, 0)
        if depthwise:
            m = m[:, None, None]
        nxt = layers[i + 1] if i + 1 < len(layers) else None
        if nxt is not None and nxt["op"] == "avgpool" and nxt.get("fused"):
            kh, kw = nxt["kernel"]
            acc = _window_sum(acc, nxt["kernel"], nxt["stride"])
            m = m / jnp.float32(kh * kw)
            name = nxt["name"]
        vals[name] = q(_requant(acc, m))
    return vals[layers[-1]["name"]].astype(jnp.int8)


def logits(cfg: dict, weights: dict, x, bits: int = 8, block: int = 256) -> np.ndarray:
    """:func:`forward` over host array ``x`` in blocks of ``block`` rows on the
    default device, one compiled program for every block; a host array."""
    fn = jax.jit(functools.partial(forward, cfg, bits=bits))
    out = []
    for start in range(0, len(x), block):
        part = x[start:start + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.zeros((pad, *part.shape[1:]), part.dtype)])
        out.append(np.asarray(fn(weights, part))[: block - pad])
    return np.concatenate(out) if out else np.zeros((0, cfg["classes"]), np.int8)
