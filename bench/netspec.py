"""A configuration file's layer list, walked for shapes and sizes.

Shared by the work counts (``work.py``), the plain reference
(``reference.py``) and the code that builds the program's model (``program.py``).
Imports nothing of the program.

Layer entries (``configs/<name>.json``, key ``layers``) are listed in
topological order. Each has a unique ``name``, which stands for its output
after its own ``relu``; ``input`` names the network's input. An entry may
name its inputs (``"inputs": ["stem", "b1c2"]``); without the key it takes
the entry before it, so a list without any ``inputs`` is a chain. Naming an
entry that comes later, or no entry at all, is an error. Ops:

* ``conv``: dense convolution, ``out`` channels, ``kernel``/``stride``/
  ``padding`` as ``[h, w]``, ``relu``; int8 weights, one requantization
  multiplier for the layer.
* ``dwconv``: depthwise convolution (one filter per channel), same keys
  without ``out``; one multiplier per channel.
* ``add``: residual join of two or more inputs of one shape, optional
  ``relu``; one f32 multiplier per input. Each input is requantized on its
  own, ``round_half_even(f32(x_i) * m_i)`` saturated to [-128, 127], the
  int32 sum of those saturated to [-128, 127], and the ReLU taken on that.
* ``maxpool``: max pool over int8 values, ``kernel``/``stride``/``padding``;
  padding is -128, so it never wins. It pools after the requantization of
  its input; the program may fuse it into the conv before it, which agrees
  because requantization is monotone.
* ``avgpool``: average pool, ``kernel``/``stride``. ``fused: true`` says the
  pool is folded into the conv before it: the window sum is taken over that
  conv's ReLU'd int32 accumulators and requantized once with the conv's
  multiplier divided by the window size. A fused pool comes right after a
  conv with ``relu`` whose output nothing else reads.
* ``fc``: dense classifier over the flattened map, ``out`` logits, no ReLU.

Convolutions and the classifier count their MACs; joins and pools count 0.
The last entry gives ``(classes,)``.
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


def _pooled(shape, entry) -> tuple:
    c, h, w = shape
    (kh, kw), (sh, sw) = entry["kernel"], entry["stride"]
    ph, pw = entry.get("padding", (0, 0))
    return (c, (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1)


def walk(cfg: dict) -> list:
    """One dict per layer: the entry plus ``inputs`` (the names it reads),
    ``in_shapes`` and ``in_shape`` (the first of them), ``out_shape``,
    ``w_shape`` and ``fan_in`` (weighted layers), ``macs`` and
    ``multipliers`` (1, the channel count for ``dwconv``, the input count
    for ``add``)."""
    shapes = {"input": tuple(cfg["input"])}
    prev = "input"
    listed = {e["name"] for e in cfg["layers"]}
    swallowed = set()  # convs whose output only a fused pool reads
    out = []
    for entry in cfg["layers"]:
        op, name = entry["op"], entry["name"]
        if name in shapes:
            raise ValueError(f"{cfg['name']}: layer name {name!r} used twice")
        inputs = list(entry.get("inputs", [prev]))
        for src in inputs:
            if src not in shapes:
                what = "a later entry" if src in listed else "no entry"
                raise ValueError(f"{cfg['name']}: {name!r} reads {src!r}, {what}")
            if src in swallowed:
                raise ValueError(f"{cfg['name']}: {name!r} reads {src!r}, which only the "
                                 f"fused pool after it may read")
        in_shapes = [shapes[src] for src in inputs]
        if op != "add" and len(inputs) != 1:
            raise ValueError(f"{cfg['name']}: {op} {name!r} takes one input, got {inputs}")
        shape = in_shapes[0]
        c, h, w = shape if len(shape) == 3 else (None, None, None)
        layer = dict(entry, inputs=inputs, in_shapes=in_shapes, in_shape=shape, macs=0)
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
        elif op == "add":
            if len(inputs) < 2 or any(s != shape for s in in_shapes):
                raise ValueError(f"{cfg['name']}: add {name!r} needs two or more inputs "
                                 f"of one shape, got {in_shapes}")
            layer["multipliers"] = len(inputs)
        elif op in ("maxpool", "avgpool"):
            if entry.get("fused"):
                before = out[-1] if out else {}
                if (op != "avgpool" or inputs != [before.get("name")]
                        or before.get("op") not in ("conv", "dwconv") or not before.get("relu")):
                    raise ValueError(f"{cfg['name']}: fused {op} {name!r} must come right "
                                     f"after the conv with relu it reads")
                swallowed.add(inputs[0])
            shape = _pooled(shape, entry)
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
        shapes[name] = shape
        prev = name
        out.append(layer)
    if shape != (cfg["classes"],):
        raise ValueError(f"{cfg['name']}: output shape {shape} is not ({cfg['classes']},)")
    return out


def is_chain(cfg: dict) -> bool:
    """Whether every layer reads the one before it, and only that."""
    before = ["input"] + [e["name"] for e in cfg["layers"]]
    return all(layer["inputs"] == [before[i]] for i, layer in enumerate(walk(cfg)))
