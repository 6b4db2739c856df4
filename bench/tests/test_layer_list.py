"""A layer list that branches and joins (named ``inputs``, ``add``,
``maxpool``) through every module of the harness, on the CPU; and pins that
show the chain configurations read as they did before the format grew.

``testdata/branching_int8.json`` is a small branching list, not a
configuration of ``BENCHMARK.json``: a conv+ReLU+max-pool stem, an identity
residual block, a stride-2 block with a 1x1 conv shortcut, a standalone
average pool after the join, and a classifier.
"""
from __future__ import annotations

import copy
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import limits  # noqa: E402
import loadgen  # noqa: E402
import netspec  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import seeded  # noqa: E402
import work  # noqa: E402
from repro.core import quantize  # noqa: E402
from repro.core.graph import FusedConvPool, MaxPool2d  # noqa: E402
from repro.quant.exec import make_int8_executor  # noqa: E402

FIXTURE = "bench/testdata/branching_int8.json"
BRANCHING = json.loads((ROOT / FIXTURE).read_text())
SEEDS = [2**33 + 5, 2**40 + 17, 3]


def _images(cfg, n, seed=3):
    size = n * int(np.prod(cfg["input"]))
    return np.frombuffer(np.random.default_rng(seed).bytes(size), np.int8).reshape(
        n, *cfg["input"])


def _unfused_maxpool(cfg):
    """The fixture with a stem that has no ReLU and a padded 3x3/s2 max pool:
    the program keeps that pool standalone, over negative values, so its
    -128 padding is in play."""
    cfg = copy.deepcopy(cfg)
    cfg["layers"][0]["relu"] = False
    cfg["layers"][1].update(kernel=[3, 3], padding=[1, 1])
    return cfg


@pytest.fixture(scope="module", params=SEEDS)
def branching(request):
    weights = seeded.draw(BRANCHING, request.param)
    return BRANCHING, weights, _images(BRANCHING, 16), program.build(BRANCHING, weights)


def _program_logits(built, x):
    fn, params = make_int8_executor(built.qm, built.plan)
    y = np.asarray(fn(params, jnp.asarray(x)))
    sim = np.asarray(jax.vmap(lambda v: quantize.simulate_int8_dag_forward(built.qm, v))(
        jnp.asarray(x)))
    np.testing.assert_array_equal(y, sim)
    return y


def test_program_matches_reference_bit_for_bit(branching):
    cfg, weights, x, built = branching
    y = _program_logits(built, x)
    np.testing.assert_array_equal(y, reference.logits(cfg, weights, x))
    # Logits spread over the int8 range, not pinned to a rail.
    assert 4 < np.abs(y.astype(int)).mean() < 100


def test_standalone_padded_maxpool_matches_reference():
    cfg = _unfused_maxpool(BRANCHING)
    weights = seeded.draw(cfg, SEEDS[0])
    built = program.build(cfg, weights)
    pools = [n for n in built.qm.graph.nodes if isinstance(n.layer, MaxPool2d)]
    assert [n.name for n in pools] == ["pool1"]
    x = _images(cfg, 16)
    np.testing.assert_array_equal(_program_logits(built, x), reference.logits(cfg, weights, x))


def test_control_fails_the_limit(branching):
    cfg, weights, x, _ = branching
    control = reference.logits(cfg, weights, x, bits=4)
    assert run.logit_gap(cfg, weights, x, control) > 3 * max(run.LOGIT_GAP_LIMIT, 1)


def test_joins_carry_the_drawn_multipliers(branching):
    _, weights, _, built = branching
    assert sorted(built.qm.joins) == ["b1add", "b2add"]
    for name, join in built.qm.joins.items():
        m = np.asarray(weights[name]["m"])
        assert m.shape == (2,) and m.dtype == np.float32
        assert np.array_equal(np.asarray(join.multipliers, np.float32), m)


def test_work_counts_and_fusion_match_the_program():
    cfg = BRANCHING
    built = program.build(cfg, seeded.draw(cfg, SEEDS[0]))
    g = built.graph
    shapes = g.shapes()
    macs = sum(n.layer.macs(shapes[n.inputs[0]]) for n in g.nodes if n.inputs)
    assert work.macs_per_image(cfg) == macs
    fused = {n.name: n.layer for n in built.qm.graph.nodes}
    assert isinstance(fused["stem+pool1"], FusedConvPool)
    assert fused["stem+pool1"].pool == "max"
    assert built.qm.graph.node("b1add").inputs == ("stem+pool1", "b1c2")
    params = sum(q.w_q.size + 4 * q.b_q.size + 4 * np.size(q.multiplier)
                 for q in built.qm.layers.values())
    params += sum(4 * len(j.multipliers) for j in built.qm.joins.values())
    assert work.param_bytes(cfg) == params
    assert built.plan.arena_elems > 0 and program.segment_count(built) > 0


def _entry(cfg, name):
    return next(e for e in cfg["layers"] if e["name"] == name)


def _forward_reference(cfg):
    _entry(cfg, "b1add")["inputs"] = ["pool1", "b2c1"]


def _unknown_name(cfg):
    _entry(cfg, "b2sc")["inputs"] = ["b1sum"]


def _fused_pool_with_two_readers(cfg):
    cfg["layers"][1].update(op="avgpool", fused=True)
    _entry(cfg, "b1c1")["inputs"] = ["stem"]


def _join_of_two_shapes(cfg):
    _entry(cfg, "b2add")["inputs"] = ["b2c2", "b1add"]


@pytest.mark.parametrize("breaks,says", [
    (_forward_reference, "a later entry"), (_unknown_name, "no entry"),
    (_fused_pool_with_two_readers, "only the fused pool"), (_join_of_two_shapes, "of one shape"),
])
def test_walk_refuses_a_broken_list(breaks, says):
    cfg = copy.deepcopy(BRANCHING)
    breaks(cfg)
    with pytest.raises(ValueError, match=says):
        netspec.walk(cfg)


def test_streams_refuse_a_branching_list():
    cfg = BRANCHING
    built = program.build(cfg, seeded.draw(cfg, SEEDS[0]))
    drv = loadgen.runner(loadgen.load("streams"), cfg, built, SEEDS[0])
    with pytest.raises(ValueError, match="streams traffic needs a chain of layers"):
        drv.setup()
    assert not netspec.is_chain(cfg)


def _bench_with_branching():
    """``BENCHMARK.json`` with the fixture's configuration and two cells added."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": BRANCHING["name"], "file": FIXTURE})
    for traffic in ("bulk", "poisson"):
        bench["workloads"].append({"name": f"branching.{traffic}", "config": BRANCHING["name"],
                                   "traffic": traffic, "chips": 1})
    return bench


SMALL = {"bulk": {"batch": 8, "pool_batches": 2, "check_rows": 16},
         "poisson": {"rate_per_s": 300, "check_rows": 64}}


@pytest.mark.parametrize("traffic", sorted(SMALL))
def test_whole_run_on_a_branching_list_is_correct(traffic):
    result, window = run.run(f"branching.{traffic}", SEEDS[1], 1.0, False,
                             bench=_bench_with_branching(), require_tpu=False,
                             traffic_override=SMALL[traffic])
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert window["compiles_in_window"] == 0


def test_limits_readings_on_a_branching_list(monkeypatch):
    monkeypatch.setattr(run, "spec", _bench_with_branching)
    real_load = loadgen.load
    monkeypatch.setattr(loadgen, "load", lambda name: {**real_load(name), **SMALL[name]})
    r = limits.readings("branching.bulk", SEEDS[2], 0.5)
    assert r["program"] == 0 and r["missing"] == 0
    assert r["control"] > 3 * max(run.LOGIT_GAP_LIMIT, 1)


# sha256 digests of what the chain configurations read, computed with this
# digest code on the harness as it stood before the layer list could name
# its inputs: the weights ``seeded.draw`` makes from seed 2**33 + 5, the
# reference's logits on eight images from ``default_rng(7)``, and the fused
# graph's nodes with the arena plan's buffers.
PINS = {
    "ds_cnn_kws_int8": (
        "1705ad3062fc987b66865bf16bc5f731c0e87a3eb469eca904b8d9906888b684",
        "e063caefe3c11239ab1cc14e8edce1f68ce58190ccd7d97d9584485ddf3f5016",
        "3613e3683dd7a46692e71ea9f50e40f4ef604f955f14df1c18c310c5dd52095f"),
    "mobilenet_v1_025_vww_int8": (
        "db889fc28ca51d8d022a9faa3061018db1c87ad2678c130651a6b5690e907a8d",
        "c0db9daa3837db4a977f719f49fc5bac9717856074d083cd934ef7eae10d33bd",
        "01a80cc4637b0c47b0faad4b687e81173a6fb701e193873dec94b9a9c2733565"),
}
# ``work.param_bytes`` on that harness.
PARAM_BYTES = {"ds_cnn_kws_int8": 25_416, "mobilenet_v1_025_vww_int8": 224_084}


def _weights_digest(weights):
    h = hashlib.sha256()
    for name in sorted(weights):
        for k in sorted(weights[name]):
            a = np.asarray(weights[name][k])
            h.update(f"{name}.{k}:{a.dtype}:{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINS))
def test_chain_configurations_read_as_before(name):
    cfg = netspec.load(name)
    weights = seeded.draw(cfg, 2**33 + 5)
    x = _images(cfg, 8, seed=7)
    logits = np.ascontiguousarray(reference.logits(cfg, weights, x))
    built = program.build(cfg, weights)
    graph_plan = (repr([(n.name, n.inputs) for n in built.qm.graph.nodes]).encode()
                  + repr(built.plan.buffers).encode())
    assert (_weights_digest(weights), hashlib.sha256(logits.tobytes()).hexdigest(),
            hashlib.sha256(graph_plan).hexdigest()) == PINS[name]
    assert work.param_bytes(cfg) == PARAM_BYTES[name]
    assert netspec.is_chain(cfg)
