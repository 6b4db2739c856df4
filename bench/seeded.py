"""Int8 weights, int32 biases and f32 requantization multipliers from a seed.

Made on the device in one jitted call, in the types they are served in.
Weights are uniform in [-127, 127], biases uniform in +-half the
accumulator's RMS. The multipliers are sized from each layer's fan-in and
the RMS of the output it reads, so that activations spread over the int8
range (about 32 LSB before the ReLU) instead of saturating or rounding to
zero; a join's multipliers give each input an equal share of that sum. A
factor in [0.8, 1.25] drawn from the seed varies them.
The plain reference and the program's model are both built from what this
returns, and nothing else.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from netspec import walk

# Target RMS of a layer's requantized pre-activation, in int8 LSB.
TARGET_RMS = 32.0
# RMS of int8 weights drawn uniformly from [-127, 127].
_W_RMS = math.sqrt((127 * 128 * 255 / 3) / 255)
# RMS of uniform int8 input images.
_X_RMS = math.sqrt(sum(v * v for v in range(-128, 128)) / 256)


def key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (more than 32 bits allowed)."""
    k = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        k = jax.random.fold_in(k, np.uint32(word & 0xFFFFFFFF))
    return k


def layout(cfg: dict) -> list:
    """``(name, w_shape, n_multipliers, base multiplier, bias range)`` per
    weighted layer and ``add``, in list order. A join has no ``w_shape``
    (``None``), one base per input and no bias."""
    out = []
    rms = {"input": _X_RMS}  # RMS of each named output, in int8 LSB
    for layer in walk(cfg):
        op, name = layer["op"], layer["name"]
        rms_in = [rms[src] for src in layer["inputs"]]
        if "w_shape" in layer:
            acc_rms = math.sqrt(layer["fan_in"]) * _W_RMS * rms_in[0]
            out.append((name, tuple(layer["w_shape"]), layer["multipliers"],
                        TARGET_RMS / acc_rms, int(acc_rms / 2)))
        elif op == "add":
            share = math.sqrt(len(rms_in))
            out.append((name, None, len(rms_in), tuple(TARGET_RMS / (r * share) for r in rms_in),
                        0))
        if op == "avgpool":
            # The mean of ReLU'd values, about 0.4 T, dominates their average.
            rms[name] = TARGET_RMS * 0.4
        elif op == "maxpool":
            # The largest of a window of ReLU'd values sits near the RMS
            # they had before the ReLU.
            rms[name] = rms_in[0] * math.sqrt(2)
        else:
            # After the ReLU an N(0, T^2) activation has RMS T/sqrt(2).
            rms[name] = TARGET_RMS * (1 / math.sqrt(2) if layer.get("relu") else 1.0)
    return out


def draw(cfg: dict, seed: int) -> dict:
    """``{layer name: {"w": int8, "b": int32, "m": f32}}`` on the device;
    ``m`` is a scalar, or one per channel for ``dwconv``; a join has only
    ``m``, one per input. One draw of random bits, cut into every tensor,
    keeps the program small to trace."""
    spec = tuple(layout(cfg))
    sizes = [(math.prod(w_shape), w_shape[0], n_m) if w_shape else (0, 0, n_m)
             for _, w_shape, n_m, _, _ in spec]
    total = sum(sum(s) for s in sizes)

    @jax.jit
    def make(k):
        bits = jax.random.bits(k, (total,), jnp.uint32)
        out, at = {}, 0

        def take(n):
            nonlocal at
            at += n
            return bits[at - n:at]

        for (name, w_shape, n_m, base, b_range), (n_w, n_b, _) in zip(spec, sizes):
            p = out[name] = {}
            if w_shape:
                w = (take(n_w) % 255).astype(jnp.int32) - 127
                b = (take(n_b) % (2 * b_range + 1)).astype(jnp.int32) - b_range
                p.update(w=w.astype(jnp.int8).reshape(w_shape), b=b)
            u = (take(n_m) >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
            m = (jnp.asarray(base, jnp.float32) * (0.8 + 0.45 * u)).astype(jnp.float32)
            p["m"] = m if n_m > 1 else m[0]
        return out

    return make(key(seed))
