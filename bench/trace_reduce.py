"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* ``window_s``: length of the host annotation ``bench.window`` that the
  harness wraps around the measured window.
* ``busy_s``: per chip, the union of the intervals in which an operation ran
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to the
  window; averaged over the chips the cell uses.
* ``modules``: per XLA module (line ``XLA Modules``), the device seconds and
  the number of runs that started in the window, on the first chip.
* ``device_ops``: the operations that took most device time, as
  ``[name, seconds]``.
* ``idle_gaps``: device idle time in the window, summed by what the host was
  doing: the ``bench.*`` annotation (other than the window) that covers the
  middle of each gap, or ``"host: none"``.
"""
from __future__ import annotations

import bisect
import collections
import re

WINDOW = "bench.window"
TOP = 10


def _union(intervals):
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _op_name(name: str) -> str:
    """``%fusion.36 = s8[1024,8,48,48]{0,1,3,2:T(8,128)} fusion(...)`` ->
    ``fusion.36 s8[1024,8,48,48]``."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name
    shape = "tuple" if rest.startswith("(") else re.sub(r"[{(].*", "", rest.split(" ", 1)[0])
    return f"{head.lstrip('%')} {shape}"


def _module_name(name: str) -> str:
    """``jit__exec(123)`` -> ``jit__exec``."""
    return re.sub(r"\(\d+\)$", "", name)


def events(path: str) -> dict:
    """``{"host": [(name, start_ns, end_ns)], "devices": {n: {"ops": [...],
    "modules": [...]}}}`` read from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                key: [(e.name, e.start_ns, e.end_ns) for e in lines[line].events]
                if line in lines else []
                for key, line in (("ops", "XLA Ops"), ("modules", "XLA Modules"))
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                         if e.name.startswith("bench.")]
    return {"host": host, "devices": devices}


def reduce(ev: dict, chips: int = 1) -> dict:
    """The numbers above from :func:`events`' output."""
    windows = [(s, e) for name, s, e in ev["host"] if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found {len(windows)}")
    w0, w1 = windows[0]
    devs = sorted(ev["devices"])[:chips]
    if not devs:
        raise ValueError("the trace has no TPU device plane")

    def clip(items):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in items if e > w0 and s < w1]

    busy, first_busy = [], None
    for d in devs:
        merged = _union([(s, e) for _, s, e in clip(ev["devices"][d]["ops"])])
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged

    op_time = collections.Counter()
    for name, s, e in clip(ev["devices"][devs[0]]["ops"]):
        op_time[_op_name(name)] += e - s
    modules = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    for name, s, e in ev["devices"][devs[0]]["modules"]:
        if w0 <= s < w1:
            rec = modules[_module_name(name)]
            rec["s"] += (e - s) * 1e-9
            rec["n"] += 1

    # The harness's own spans inside the window do not nest, so the one that
    # covers a time is the latest that started before it, if it has not ended.
    spans = sorted((s, e, name) for name, s, e in ev["host"] if name != WINDOW)
    starts = [s for s, _, _ in spans]
    gap_time = collections.Counter()
    edges = [w0] + [t for iv in first_busy for t in iv] + [w1]
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        label = spans[i][2] if i >= 0 and spans[i][1] > mid else "host: none"
        gap_time[label] += e - s

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "modules": dict(modules),
        "device_ops": [[n, t * 1e-9] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[n, t * 1e-9] for n, t in gap_time.most_common(TOP)],
    }


def idle_pct(summary) -> float | None:
    """Device idle share of the traced window, in %; None without a trace."""
    if not summary or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
