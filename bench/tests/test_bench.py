"""The benchmark's own checks, on the CPU in a minute or two.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
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

import netspec  # noqa: E402
import peaks  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import seeded  # noqa: E402
import work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
CONFIGS = [c["name"] for c in SPEC["configs"]]


def test_spec_resolves_to_files():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert netspec.load(c["name"])["name"] == c["name"]
        assert c["source"] == netspec.load(c["name"])["source"]
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] == 1
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert callable(run.reader(m["name"]))


def test_names_units_and_keys():
    names = [c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    for name in names:
        assert NAME.fullmatch(name), name
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_cell_reports_what_its_metrics_move():
    for w in SPEC["workloads"]:
        e2e, per_layer = run.cell_metrics(SPEC, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert per_layer, w["name"]
        for m in per_layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_per_layer_metric_without_workloads_follows_what_it_moves():
    bench = {"end_to_end": SPEC["end_to_end"],
             "per_layer": [{"name": "x", "moves": "images_per_s"},
                           {"name": "y", "moves": "request_p95_ms", "workloads": []}]}
    assert [m["name"] for m in run.cell_metrics(bench, "kws_int8.bulk")[1]] == ["x"]
    assert run.cell_metrics(bench, "kws_int8.serve")[1] == []


@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_match_the_program(name):
    cfg = netspec.load(name)
    built = program.build(cfg, _zero_weights(cfg))
    g = built.graph
    shapes = g.shapes()
    macs = sum(n.layer.macs(shapes[n.inputs[0]]) for n in g.nodes if n.inputs)
    assert work.macs_per_image(cfg) == macs == cfg["expect"]["macs_per_image"]
    assert g.param_count() == cfg["expect"]["params"]
    assert built.plan.arena_elems == cfg["expect"]["arena_bytes_int8"]
    assert program.segment_count(built) == cfg["expect"]["segments"]
    assert work.call(cfg, 2)["ops"] == 4 * macs


def test_published_kws_and_vww_counts():
    assert work.macs_per_image(netspec.load("ds_cnn_kws_int8")) == 2_656_768
    assert work.macs_per_image(netspec.load("mobilenet_v1_025_vww_int8")) == 7_489_664


def _zero_weights(cfg):
    return {n: {"w": np.zeros(ws, np.int8), "b": np.zeros(ws[0], np.int32),
                "m": np.full((k,) if k > 1 else (), 0.01, np.float32)}
            for n, ws, k, _, _ in seeded.layout(cfg)}


@pytest.fixture(scope="module", params=CONFIGS)
def drawn(request):
    cfg = netspec.load(request.param)
    weights = seeded.draw(cfg, 2**33 + 5)
    x = np.frombuffer(np.random.default_rng(3).bytes(6 * int(np.prod(cfg["input"]))),
                      np.int8).reshape(6, *cfg["input"])
    return cfg, weights, x


def test_executor_matches_simulator_and_reference(drawn):
    from repro.core import quantize
    from repro.quant.exec import make_int8_executor

    cfg, weights, x = drawn
    built = program.build(cfg, weights)
    fn, params = make_int8_executor(built.qm, built.plan)
    y = np.asarray(fn(params, jnp.asarray(x)))
    sim = np.asarray(jax.vmap(lambda v: quantize.simulate_int8_dag_forward(built.qm, v))(
        jnp.asarray(x)))
    np.testing.assert_array_equal(y, sim)
    assert run.logit_gap(cfg, weights, x, y) == 0
    # Logits spread over the int8 range, not pinned to a rail.
    assert 4 < np.abs(y.astype(int)).mean() < 100


def test_control_fails_the_limit(drawn):
    """The int4 control reads far above the limit (kept small here; the chip
    readings at the cells' own sizes are in PERF.md)."""
    cfg, weights, x = drawn
    control = reference.logits(cfg, weights, x, bits=4)
    assert run.logit_gap(cfg, weights, x, control) > 3 * max(run.LOGIT_GAP_LIMIT, 1)


def test_seeds_draw_alike_and_apart():
    cfg = netspec.load("ds_cnn_kws_int8")
    a, b = seeded.draw(cfg, 2**40 + 1), seeded.draw(cfg, 2**40 + 1)
    c = seeded.draw(cfg, 2**40 + 2)
    assert all(np.array_equal(a[k]["w"], b[k]["w"]) for k in a)
    assert not np.array_equal(a["conv1"]["w"], c["conv1"]["w"])
    assert a["dw1"]["m"].shape == (64,) and a["dw1"]["w"].dtype == jnp.int8


def test_unknown_device_kind_raises():
    assert peaks.for_kind("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


def _run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kws_int8.bulk", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
