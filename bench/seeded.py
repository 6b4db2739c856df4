"""Int8 weights, int32 biases and f32 requantization multipliers from a seed.

Made on the device in one jitted call, in the types they are served in.
Weights are uniform in [-127, 127], biases uniform in +-half the
accumulator's RMS. The multipliers are sized from each layer's fan-in so
that activations spread over the int8 range (about 32 LSB before the ReLU)
instead of saturating or rounding to zero; a factor in [0.8, 1.25] drawn
from the seed varies them.
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
    weighted layer."""
    out = []
    rms_in = _X_RMS
    layers = walk(cfg)
    for i, layer in enumerate(layers):
        if "w_shape" not in layer:
            continue
        acc_rms = math.sqrt(layer["fan_in"]) * _W_RMS * rms_in
        out.append((layer["name"], tuple(layer["w_shape"]), layer["multipliers"],
                    TARGET_RMS / acc_rms, int(acc_rms / 2)))
        # After the ReLU an N(0, T^2) activation has RMS T/sqrt(2); after a
        # fused average its mean, about 0.4 T, dominates.
        fused_pool = i + 1 < len(layers) and layers[i + 1]["op"] == "avgpool"
        rms_in = TARGET_RMS * (0.4 if fused_pool else 1 / math.sqrt(2))
    return out


def draw(cfg: dict, seed: int) -> dict:
    """``{layer name: {"w": int8, "b": int32, "m": f32}}`` on the device;
    ``m`` is a scalar, or one per channel for ``dwconv``. One draw of random
    bits, cut into every tensor, keeps the program small to trace."""
    spec = tuple(layout(cfg))
    sizes = [(math.prod(w_shape), w_shape[0], n_m) for _, w_shape, n_m, _, _ in spec]
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
            w = (take(n_w) % 255).astype(jnp.int32) - 127
            b = (take(n_b) % (2 * b_range + 1)).astype(jnp.int32) - b_range
            u = (take(n_m) >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
            m = (base * (0.8 + 0.45 * u)).astype(jnp.float32)
            out[name] = {"w": w.astype(jnp.int8).reshape(w_shape), "b": b,
                         "m": m if n_m > 1 else m[0]}
        return out

    return make(key(seed))
