"""The one general traffic generator: reads a mix from ``traffic/<name>.json``
and drives the program's entry point with it.

Three kinds of mix, named by the file's ``kind``:

* ``bulk``: closed loop over the int8 arena executor (``make_int8_executor``
  through ``pingpong.aot_compile``) at ``batch`` images, one batch in
  flight; the host cycles a pool of ``pool_batches`` batches, H2D and D2H
  inside the window.
* ``requests``: open loop of single-image requests into ``CNNEngine``
  (``from_quantized(...).submit``), ``rate_per_s`` Poisson arrivals per
  second: their times are the order statistics of uniform draws over the
  window (a Poisson process with its count fixed, so every seed sends the
  same number). Latency runs from the due time.
* ``streams``: ``streams`` concurrent sessions on ``StreamServer``
  (``from_quantized(...).push``), one ``frame`` every ``hop_ms`` per
  stream at a uniform random phase (one draw for every seed); only for a
  configuration whose layers form a chain (``StreamServer`` runs no joins).
  Latency of an emission runs from the due time of the frame that caused
  it. The generator, the mix's only thread, spins to each due time, so that
  no sleep adds lateness of its own.

Every runner: ``setup()`` compiles and warms exactly the shapes the mix
uses; ``window(seconds)`` measures and returns the window's record;
``sample(n)`` returns ``(inputs, served outputs, missing)`` for a sample of
what the window produced, drawn from the seed; ``close()`` frees the
program's state.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import netspec
from repro.core import pingpong
from repro.quant.exec import make_int8_executor
from repro.serve.cnn_engine import CNNEngine, CoalescePolicy, StreamServer

BENCH = Path(__file__).resolve().parent
annotate = jax.profiler.TraceAnnotation
# Seconds of the mix's own traffic run in set-up, before the window, so the
# window starts on a warm server.
WARMUP_S = 0.5


def load(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic file {path}")
    return json.loads(path.read_text())


def int8_noise(rng: np.random.Generator, shape) -> np.ndarray:
    """Uniform int8 values, made in bulk from raw bytes."""
    n = int(np.prod(shape))
    return np.frombuffer(rng.bytes(n), np.int8).reshape(shape)


def pct(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of ``values``."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class Bulk:
    def __init__(self, traffic, cfg, built, seed):
        self.t, self.cfg, self.built, self.seed = traffic, cfg, built, seed
        self.batch = int(traffic["batch"])

    def setup(self):
        fn, self.params = make_int8_executor(self.built.qm, self.built.plan)
        shape = (self.batch, *self.cfg["input"])
        self.compiled = pingpong.aot_compile(fn, self.params, shape, jnp.int8)
        self.module = self.compiled.as_text().split(None, 2)[1].rstrip(",")
        rng = np.random.default_rng([self.seed, 1])
        self.pool = [int8_noise(rng, shape) for _ in range(int(self.t["pool_batches"]))]
        for x in self.pool[:2]:
            np.asarray(self.compiled(self.params, jax.device_put(x)))

    def window(self, seconds):
        outs, call_s = [], []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while True:
            t_call = time.perf_counter()
            x = self.pool[len(outs) % len(self.pool)]
            with annotate("bench.h2d"):
                xd = jax.device_put(x)
            with annotate("bench.dispatch"):
                y = self.compiled(self.params, xd)
            with annotate("bench.d2h"):
                outs.append(np.asarray(y))
            call_s.append(time.perf_counter() - t_call)
            if time.perf_counter() >= t_end:
                break
        window_s = time.perf_counter() - t0
        self.outs = outs
        images = len(outs) * self.batch
        return {"window_s": window_s, "attempted": images, "failed": 0,
                "images": images, "calls": len(outs), "batch": self.batch,
                "executor_module": self.module,
                "call_p50_ms": pct(call_s, 50) * 1e3, "call_max_ms": max(call_s) * 1e3,
                "images_per_s": images / window_s}

    def sample(self, n):
        rng = np.random.default_rng([self.seed, 2])
        calls = rng.integers(0, len(self.outs), n)
        calls[-1] = len(self.outs) - 1
        rows = rng.integers(0, self.batch, n)
        x = np.stack([self.pool[c % len(self.pool)][r] for c, r in zip(calls, rows)])
        y = np.stack([self.outs[c][r] for c, r in zip(calls, rows)])
        return x, y, 0

    def close(self):
        del self.compiled, self.params


class Requests:
    def __init__(self, traffic, cfg, built, seed):
        self.t, self.cfg, self.built, self.seed = traffic, cfg, built, seed

    def setup(self):
        t = self.t
        self.engine = CNNEngine.from_quantized(
            self.built.qm, self.built.plan, buckets=tuple(t["buckets"]),
            policy=CoalescePolicy(max_batch=int(t["max_batch"]),
                                  max_wait_s=t["max_wait_ms"] * 1e-3))
        self.engine.start()
        rng = np.random.default_rng([self.seed, 1])
        self.pool = int8_noise(rng, (int(t["pool"]), *self.cfg["input"]))
        # Each bucket once: bursts of exactly that many requests.
        for b in t["buckets"]:
            for r in [self.engine.submit(self.pool[i]) for i in range(b)]:
                r.result(timeout=60)
        self.window(WARMUP_S)

    def schedule(self, seconds):
        """``(due offsets in s, pool index)`` for the window, from the seed."""
        rng = np.random.default_rng([self.seed, 3])
        n = int(round(self.t["rate_per_s"] * seconds))
        due = np.sort(rng.uniform(0.0, seconds, n))
        return due, rng.integers(0, len(self.pool), n)

    def window(self, seconds):
        due, idx = self.schedule(seconds)
        before = self.engine.stats.snapshot()
        reqs, late = [], np.empty(len(due))
        t0 = time.perf_counter() + 0.005
        with annotate("bench.wait"):
            time.sleep(0.005)
        for i in range(len(due)):
            at = t0 + due[i]
            delay = at - time.perf_counter()
            if delay > 0:
                with annotate("bench.wait"):
                    time.sleep(delay)
            with annotate("bench.submit"):
                late[i] = time.perf_counter() - at
                reqs.append(self.engine.submit(self.pool[idx[i]]))
        failed = 0
        for r in reqs:
            try:
                r.result(timeout=60)
            except (TimeoutError, RuntimeError):
                failed += 1
        window_s = time.perf_counter() - t0
        after = self.engine.stats.snapshot()
        self.reqs, self.idx = reqs, idx
        lat = [r.t_done - (t0 + d) for r, d in zip(reqs, due) if r.error is None and r.y is not None]
        return {"window_s": window_s, "attempted": len(reqs), "failed": failed,
                "latency_s": lat,
                "requests": after.requests - before.requests,
                "batches": after.batches - before.batches,
                "late_p95_ms": pct(late, 95) * 1e3, "late_max_ms": float(late.max()) * 1e3,
                "late_max_at_s": float(due[late.argmax()]),
                **{f"request_p{q}_ms": pct(lat, q) * 1e3 if lat else float("nan")
                   for q in (50, 90, 95)}}

    def sample(self, n):
        rng = np.random.default_rng([self.seed, 2])
        pick = rng.choice(len(self.reqs), min(n, len(self.reqs)), replace=False)
        done = [i for i in pick if self.reqs[i].y is not None]
        x = self.pool[self.idx[done]]
        y = (np.stack([self.reqs[i].y for i in done]) if done
             else np.zeros((0, self.cfg["classes"]), np.int8))
        return x, y, sum(r.y is None for r in self.reqs)

    def close(self):
        self.engine.stop()
        del self.engine


class Streams:
    def __init__(self, traffic, cfg, built, seed):
        self.t, self.cfg, self.built, self.seed = traffic, cfg, built, seed
        self.n = int(traffic["streams"])

    def setup(self):
        if not netspec.is_chain(self.cfg):
            raise ValueError(f"{self.cfg['name']}: streams traffic needs a chain of layers; "
                             "StreamServer runs no branches or joins")
        c, _, w = self.cfg["input"]
        rng = np.random.default_rng([self.seed, 1])
        self.pool = int8_noise(rng, (int(self.t["pool"]), c, w))
        self.offset = rng.integers(0, len(self.pool), self.n)
        self.srv = StreamServer.from_quantized(self.built.qm)
        for prefix in ("w", "s"):
            for s in range(self.n):
                self.srv.open(f"{prefix}{s}")
        self.window(WARMUP_S, prefix="w")
        for s in range(self.n):
            self.srv.close(f"w{s}")

    def frame(self, s, t):
        return self.pool[(self.offset[s] + t) % len(self.pool)]

    def window(self, seconds, prefix="s"):
        hop = self.t["hop_ms"] * 1e-3
        frames = int(seconds / hop)
        # One fixed draw of phases, so that every seed offers the same
        # arrivals; the seed deals them out to the streams.
        phase = np.random.default_rng(0).uniform(0.0, hop, self.n)
        phase = phase[np.random.default_rng([self.seed, 3]).permutation(self.n)]
        due = (phase[:, None] + hop * np.arange(frames)[None, :]).ravel()
        order = np.argsort(due, kind="stable")
        who = np.repeat(np.arange(self.n), frames)
        when = np.tile(np.arange(frames), self.n)
        push_s, lat, late = [], [], np.empty(len(order))
        self.served = {}
        t0 = time.perf_counter() + 0.005
        for k, j in enumerate(order):
            s, t = int(who[j]), int(when[j])
            at = t0 + due[j]
            if time.perf_counter() < at:
                with annotate("bench.wait"):
                    while time.perf_counter() < at:
                        pass
            with annotate("bench.push"):
                start = time.perf_counter()
                y = self.srv.push(f"{prefix}{s}", self.frame(s, t))
                end = time.perf_counter()
            late[k] = start - at
            push_s.append(end - start)
            if y is not None:
                lat.append(end - at)
                self.served[(s, t)] = y
        window_s = time.perf_counter() - t0
        self.frames = frames
        expected = self.n * (frames // 2)
        return {"window_s": window_s, "attempted": expected,
                "failed": expected - len(lat), "latency_s": lat,
                "late_p95_ms": pct(late, 95) * 1e3, "late_max_ms": float(late.max()) * 1e3,
                "late_max_at_s": float(due[order[late.argmax()]]),
                "push_p50_ms": pct(push_s, 50) * 1e3,
                "emission_p90_ms": pct(lat, 90) * 1e3 if lat else float("nan"),
                "emission_p95_ms": pct(lat, 95) * 1e3 if lat else float("nan")}

    def sample(self, n):
        """Emissions to check: the last of every stream (the longest history
        carried in the rings) and others drawn from the seed; ``missing``
        counts the emissions due in the window that never came."""
        rng = np.random.default_rng([self.seed, 2])
        c, h, w = self.cfg["input"]
        keys = sorted(self.served)
        missing = self.n * (self.frames // 2) - len(keys)
        last = {}
        for s, t in keys:
            last[s] = (s, t)
        others = [keys[i] for i in rng.choice(len(keys), min(n, len(keys)), replace=False)]
        pick = list(dict.fromkeys(list(last.values()) + others))[: max(n, len(last))]
        xs = np.zeros((len(pick), c, h, w), np.int8)
        for i, (s, t) in enumerate(pick):
            hist = [self.frame(s, u) for u in range(max(0, t + 1 - h), t + 1)]
            xs[i, :, h - len(hist):, :] = np.stack(hist, axis=1)
        y = np.stack([self.served[k] for k in pick]) if pick else np.zeros((0, self.cfg["classes"]), np.int8)
        return xs, y, missing

    def close(self):
        for s in range(self.n):
            self.srv.close(f"s{s}")
        del self.srv


KINDS = {"bulk": Bulk, "requests": Requests, "streams": Streams}


def runner(traffic: dict, cfg: dict, built, seed: int):
    return KINDS[traffic["kind"]](traffic, cfg, built, seed)
