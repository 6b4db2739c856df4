"""A configuration file's layer list, walked for shapes and sizes.

Shared by the work counts (``work.py``), the plain reference
(``reference.py``) and the code that builds the program's model (``program.py``).
Imports nothing of the program.

Layer entries (``configs/<name>.json``, key ``layers``), in order:

* ``conv``: dense convolution, ``out`` channels, ``kernel``/``stride``/
  ``padding`` as ``[h, w]``, ``relu``; int8 weights, one requantization
  multiplier for the layer.
* ``dwconv``: depthwise convolution (one filter per channel), same keys
  without ``out``; one multiplier per channel.
* ``avgpool``: average pool, ``kernel``/``stride``. ``fused: true`` says the
  pool is folded into the conv before it: the window sum is taken over that
  conv's ReLU'd int32 accumulators and requantized once with the conv's
  multiplier divided by the window size.
* ``fc``: dense classifier over the flattened map, ``out`` logits, no ReLU.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The configuration named ``name`` (``configs/<name>.json``)."""
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def walk(cfg: dict) -> list:
    """One dict per layer: the entry plus ``in_shape``, ``out_shape``,
    ``w_shape`` and ``fan_in`` (weighted layers), ``macs`` and
    ``multipliers`` (1, or the channel count for ``dwconv``)."""
    shape = tuple(cfg["input"])
    out = []
    for entry in cfg["layers"]:
        op = entry["op"]
        c, h, w = shape if len(shape) == 3 else (None, None, None)
        layer = dict(entry, in_shape=shape, macs=0)
        if op in ("conv", "dwconv"):
            (kh, kw), (sh, sw), (ph, pw) = entry["kernel"], entry["stride"], entry["padding"]
            oh = (h + 2 * ph - kh) // sh + 1
            ow = (w + 2 * pw - kw) // sw + 1
            if op == "conv":
                co = entry["out"]
                layer.update(w_shape=(co, c, kh, kw), fan_in=c * kh * kw, multipliers=1)
            else:
                co = c
                layer.update(w_shape=(c, 1, kh, kw), fan_in=kh * kw, multipliers=c)
            shape = (co, oh, ow)
            layer["macs"] = co * oh * ow * layer["fan_in"]
        elif op == "avgpool":
            (kh, kw), (sh, sw) = entry["kernel"], entry["stride"]
            shape = (c, (h - kh) // sh + 1, (w - kw) // sw + 1)
        elif op == "fc":
            n_in = 1
            for d in shape:
                n_in *= d
            layer.update(w_shape=(entry["out"], n_in), fan_in=n_in, multipliers=1,
                         macs=entry["out"] * n_in)
            shape = (entry["out"],)
        else:
            raise ValueError(f"unknown layer op {op!r} in {cfg['name']}")
        layer["out_shape"] = shape
        out.append(layer)
    if shape != (cfg["classes"],):
        raise ValueError(f"{cfg['name']}: output shape {shape} is not ({cfg['classes']},)")
    return out

