"""The system under test, built from a configuration through its public IR.

``build(cfg, weights)`` turns the layer list into the program's graph
(``repro.core.graph``), fuses and plans it the way a deployment does
(``fusion.fuse_dag``, ``schedule.plan_dag`` at one byte per element), and
wraps the weights from ``seeded.draw`` in the program's ``QuantizedModel``.
With ``in_scale = out_scale = 1`` each layer's requantization multiplier is
the drawn ``m`` itself, and each ``add`` becomes a ``QuantizedJoin`` whose
multipliers are the drawn ``m`` too, so program and reference see the same
numbers.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core import fusion, schedule  # noqa: E402
from repro.core import segments as segments_mod  # noqa: E402
from repro.core.graph import (  # noqa: E402
    Add,
    AvgPool2d,
    Conv2d,
    DAGGraph,
    DepthwiseConv2d,
    Flatten,
    Input,
    Linear,
    MaxPool2d,
    Node,
    ReLU,
)
from repro.core.quantize import QuantizedJoin, QuantizedLayer, QuantizedModel  # noqa: E402

from netspec import walk  # noqa: E402


@dataclasses.dataclass
class Built:
    graph: DAGGraph        # as listed
    qm: QuantizedModel     # over the fused graph
    plan: object           # int8 arena plan (MemoryPlan)


def graph(cfg: dict) -> DAGGraph:
    """The configuration as the program's graph, unfused. A layer's ReLU is a
    node of its own, ``<name>_relu``, and what reads the layer reads that."""
    nodes = [Node(Input(shape=tuple(cfg["input"]), name="input"))]
    output = {"input": "input"}  # entry name -> the node that holds its output

    def add(layer, inputs):
        nodes.append(Node(layer, inputs))
        return layer.name

    for layer in walk(cfg):
        op, name = layer["op"], layer["name"]
        inputs = tuple(output[src] for src in layer["inputs"])
        geom = dict(kernel_size=tuple(layer.get("kernel", (1, 1))),
                    stride=tuple(layer.get("stride", (1, 1))))
        if op == "conv":
            last = add(Conv2d(layer["in_shape"][0], layer["out"],
                              padding=tuple(layer["padding"]), name=name, **geom), inputs)
        elif op == "dwconv":
            last = add(DepthwiseConv2d(layer["in_shape"][0], padding=tuple(layer["padding"]),
                                       name=name, **geom), inputs)
        elif op == "add":
            last = add(Add(name=name), inputs)
        elif op == "maxpool":
            last = add(MaxPool2d(padding=tuple(layer.get("padding", (0, 0))), name=name,
                                 **geom), inputs)
        elif op == "avgpool":
            last = add(AvgPool2d(name=name, **geom), inputs)
        elif op == "fc":
            if len(layer["in_shape"]) > 1:
                inputs = (add(Flatten(name="flatten"), inputs),)
            last = add(Linear(int(np.prod(layer["in_shape"])), layer["out"], name=name), inputs)
        if layer.get("relu"):
            last = add(ReLU(name=f"{name}_relu"), (last,))
        output[name] = last
    return DAGGraph(nodes)


def build(cfg: dict, weights: dict) -> Built:
    """Graph, fused int8 model and arena plan for ``weights``
    (``{name: {"w", "b", "m"}}``, only ``m`` for a join; device or host
    arrays)."""
    g = graph(cfg)
    fused = fusion.fuse_dag(g)
    per_channel = {layer["name"] for layer in walk(cfg) if layer["op"] == "dwconv"}
    layers, joins = {}, {}
    for node in fused.nodes:
        inner = getattr(node.layer, "conv", None) or getattr(node.layer, "linear", None)
        source = inner.name if inner is not None else node.name
        if source not in weights:
            continue
        p = weights[source]
        m = np.asarray(p["m"], np.float32)
        if isinstance(node.layer, Add):
            joins[node.name] = QuantizedJoin(name=node.name, in_scales=tuple(map(float, m)),
                                             out_scale=1.0)
            continue
        layers[node.name] = QuantizedLayer(
            name=node.name, w_q=p["w"], b_q=p["b"],
            w_scale=m if source in per_channel else float(m),
            in_scale=1.0, out_scale=1.0)
    missing = set(weights) - {q.name.split("+")[0] for q in layers.values()} - set(joins)
    if missing:
        raise ValueError(f"{cfg['name']}: no program layer for {sorted(missing)}")
    qm = QuantizedModel(graph=fused, input_scale=1.0, layers=layers, joins=joins)
    return Built(graph=g, qm=qm, plan=schedule.plan_dag(g, io_dtype_bytes=1))


def segment_count(built: Built) -> int:
    """Compiled segments of the int8 executor for this plan."""
    _, _, segs = segments_mod.segments_for_plan(built.qm.graph, built.plan)
    return len(segs)
