"""The work of one forward pass, from a configuration's layer shapes.

Operations are 2 x MACs of the convolutions and the classifier. Bytes are
the least any implementation must move: the int8 input and int8 logits of
every image, plus the parameters once per call (int8 weights, int32 biases,
f32 multipliers, a join's among them). Activation traffic is left out on purpose, so that the
roofline reads the same work however the layers are fused or laid out.
"""
from __future__ import annotations

import math

from netspec import walk


def macs_per_image(cfg: dict) -> int:
    return sum(layer["macs"] for layer in walk(cfg))


def ops_per_image(cfg: dict) -> int:
    return 2 * macs_per_image(cfg)


def param_bytes(cfg: dict) -> int:
    total = 0
    for layer in walk(cfg):
        if "w_shape" in layer:
            total += math.prod(layer["w_shape"]) + 4 * layer["w_shape"][0]
        total += 4 * layer.get("multipliers", 0)
    return total


def call(cfg: dict, batch: int) -> dict:
    """``{"ops", "bytes"}`` of one call of the executor on ``batch`` images."""
    io = math.prod(cfg["input"]) + cfg["classes"]
    return {"ops": batch * ops_per_image(cfg), "bytes": batch * io + param_bytes(cfg)}


def bound_s(cfg: dict, batch: int, peak: dict) -> tuple:
    """``(seconds, "compute" | "memory")``: the least time one call can take
    on a chip with ``peak`` (``peaks.for_kind``), and which bound sets it."""
    w = call(cfg, batch)
    t_ops = w["ops"] / peak["int8_ops"]
    t_mem = w["bytes"] / peak["hbm_bytes_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
