"""Readings that ``run.LOGIT_GAP_LIMIT`` is set from, on the chip.

    python3 bench/limits.py --workload kws_int8.bulk --seconds 2 --seeds 1 2 3 ...

For each seed, in this one process: the cell's own traffic at its own load
for a short window, then the same sample a run checks. Prints one JSON line
a seed with two readings: ``program``, the program's largest logit gap
against the int8 reference (the lower reading), and ``control``, the gap of
the reference computed at int4 against the int8 reference on the same inputs
(the upper reading).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def readings(workload: str, seed: int, seconds: float) -> dict:
    import reference
    import run

    bench = run.spec()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg, traffic, weights, drv = run.prepare(bench, cell, seed)
    drv.setup()
    drv.window(seconds)
    x, y, missing = drv.sample(traffic.get("check_rows", run.CHECK_ROWS))
    drv.close()
    control = reference.logits(cfg, weights, x, bits=4)
    return {"workload": workload, "seed": seed, "rows": len(x), "missing": missing,
            "program": run.logit_gap(cfg, weights, x, y),
            "control": run.logit_gap(cfg, weights, x, control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("limits: no TPU found", file=sys.stderr)
        return 2
    import run

    run.use_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
