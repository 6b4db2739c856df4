"""Find the load a serving cell sustains, once, on the chip.

    python3 bench/sweep.py --workload kws_int8.serve --seed 1 --seconds 5 --load 2000 4000 8000

For each load (arrivals per second for a ``requests`` mix, concurrent
streams for a ``streams`` mix) it runs one window of the cell's traffic in
this process and prints one JSON line: the tail, how late the generator
ran, and whether a backlog grew (the last quarter's median latency against
the first's). The cell's traffic file then fixes 0.8 x the highest load with
a steady tail and no backlog.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--load", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 2
    import loadgen
    import program
    import run
    import seeded

    run.use_compile_cache()
    bench = run.spec()
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((run.ROOT / config["file"]).read_text())
    traffic = loadgen.load(cell["traffic"])
    key = {"requests": "rate_per_s", "streams": "streams"}[traffic["kind"]]
    weights = seeded.draw(cfg, args.seed)
    built = program.build(cfg, weights)
    for load in args.load:
        drv = loadgen.runner({**traffic, key: int(load)}, cfg, built, args.seed)
        drv.setup()
        gc.collect()
        gc.freeze()
        rec = drv.window(args.seconds)
        drv.close()
        lat = rec.get("latency_s") or []
        tail = rec.get("request_p95_ms", rec.get("emission_p95_ms"))
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "load": load, "attempted": rec["attempted"], "failed": rec["failed"],
            "window_s": rec["window_s"], "p95_ms": tail,
            "p50_ms": loadgen.pct(lat, 50) * 1e3 if lat else None,
            "p99_ms": loadgen.pct(lat, 99) * 1e3 if lat else None,
            "first_quarter_p50_ms": float(np.median(lat[:q])) * 1e3 if lat else None,
            "last_quarter_p50_ms": float(np.median(lat[-q:])) * 1e3 if lat else None,
            "late_p95_ms": rec["late_p95_ms"], "late_max_ms": rec["late_max_ms"],
            "late_max_at_s": rec["late_max_at_s"],
            **({"requests_per_batch": rec["requests"] / max(rec["batches"], 1)}
               if "batches" in rec else {}),
            **({"push_p50_ms": rec["push_p50_ms"]} if "push_p50_ms" in rec else {}),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
