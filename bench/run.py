"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration (``configs/<config>.json``) with int8
weights drawn on the device from ``--seed``, sets up its traffic mix
(``traffic/<traffic>.json``, driven by ``loadgen``), measures for
``--seconds``, checks a sample of what the window served against the plain
int8 reference (``reference``), and prints one JSON result as the last line
of standard output. With ``--trace 1`` the window runs under the JAX
profiler and the result carries the cell's per-layer metrics, each read by
``metrics/<name>.py`` from the run's record.

Refuses to run (exit 2, no result) unless JAX finds a TPU with as many
chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# The TPU runtime writes no log files (its default is a fixed path in /tmp).
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

# Largest gap, in int8 steps, between a served logit and the reference's:
# the program promises bit-exact int8 (readings in PERF.md).
LOGIT_GAP_LIMIT = 0
CHECK_ROWS = 512


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bench: dict, workload: str) -> tuple:
    """``(end-to-end metric entries, per-layer metric entries)`` the cell reports."""
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    # A per-layer metric without ``workloads`` is read in every cell that
    # reports the end-to-end metric it moves.
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return e2e, per_layer


def reader(name: str):
    """``metrics/<name>.py``'s ``read(record) -> float | None``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Backend compilations, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, in the checkout at a fixed path,
    through the program's own switch."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.serve.step import enable_persistent_cache

    enable_persistent_cache()


def prepare(bench: dict, cell: dict, seed: int, traffic_override=None) -> tuple:
    """``(cfg, traffic, weights, runner)`` of a cell for ``seed``: weights
    drawn, the program's model built, the traffic runner not yet set up."""
    import loadgen
    import program
    import seeded

    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / config["file"]).read_text())
    traffic = {**loadgen.load(cell["traffic"]), **(traffic_override or {})}
    weights = seeded.draw(cfg, seed)
    drv = loadgen.runner(traffic, cfg, program.build(cfg, weights), seed)
    return cfg, traffic, weights, drv


def logit_gap(cfg: dict, weights: dict, x, y) -> int:
    """Largest gap, in int8 steps, between logits ``y`` and the int8
    reference's on inputs ``x``."""
    import reference

    if not len(x):
        return 0
    ref = reference.logits(cfg, weights, x)
    return int(abs(y.astype(int) - ref.astype(int)).max())


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench: dict | None = None, require_tpu: bool = True,
        traffic_override: dict | None = None) -> tuple:
    """One run; returns ``(result object, window record)``, the window
    record holding compilations in the window and how late the load
    generator ran. ``require_tpu=False`` and
    ``traffic_override`` exist for the benchmark's own CPU tests."""
    bench = bench or spec()
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")

    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"bench: cell {workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    use_compile_cache()
    compiles = CompileCounter()

    marks = {"jax_ready": time.perf_counter() - T_START}
    cfg, traffic, weights, drv = prepare(bench, cell, seed, traffic_override)
    jax.block_until_ready(weights)
    marks["model_built"] = time.perf_counter() - T_START
    drv.setup()
    # Set-up's garbage is collected now and the survivors frozen, so the
    # window does not pay for scanning them.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    marks["traffic_ready"] = setup_s

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # Device ops and the harness's own annotations; the Python tracer
        # would record every Python call of the generator.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = compiles.n
    with jax.profiler.TraceAnnotation("bench.window"):
        rec = drv.window(seconds)
    rec["compiles_in_window"] = compiles.n - before
    summary = None
    if trace:
        jax.profiler.stop_trace()
        import trace_reduce

        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        summary = trace_reduce.reduce(trace_reduce.events(str(path)), cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(jax, cell["chips"])

    # The check: a sample of what the window served, against the reference,
    # once the program's state is freed.
    x, y, missing = drv.sample(traffic.get("check_rows", CHECK_ROWS))
    drv.close()
    gap = logit_gap(cfg, weights, x, y)
    missing = int(missing) if len(x) else max(int(missing), 1)
    checks = {"logit_gap_lsb": {"value": gap, "limit": LOGIT_GAP_LIMIT},
              "rows_missing": {"value": missing, "limit": 0}}
    correct = gap <= LOGIT_GAP_LIMIT and missing == 0

    import peaks
    import work

    rec.update(setup_s=setup_s, cfg=cfg, trace=summary,
               peak=peaks.for_kind(device["kind"]) if require_tpu else None,
               ops_per_image=work.ops_per_image(cfg))
    e2e, per_layer = cell_metrics(bench, workload)
    metrics = {}
    chosen = per_layer if trace else e2e
    for m in chosen:
        value = rec.get(m["name"]) if not trace else reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    window = {k: rec[k] for k in ("setup_s", "window_s", "compiles_in_window",
                                  "late_p95_ms", "late_max_ms", "late_max_at_s",
                                  "call_p50_ms", "call_max_ms", "images_per_s",
                                  "push_p50_ms", "emission_p95_ms", "request_p50_ms",
                                  "request_p90_ms", "request_p95_ms") if k in rec}
    window["setup_marks_s"] = marks
    return result, window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, window = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps({"window": window}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
