"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>    # on one TPU chip

Three runs of a small jitted matmul chain inside a ``bench.window``
annotation, each preceded by a 2 ms ``bench.wait`` sleep, so that the trace
holds device operations, one XLA module run three times and idle gaps the
host annotations explain. Prints every event the reduction reads, for
checking its numbers by hand.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import trace_reduce  # noqa: E402


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.d2h"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    path = next(Path(tmp).rglob("*.xplane.pb"))
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        print("plane", plane.name, [(ln.name, len(list(ln.events))) for ln in plane.lines])
    ev = trace_reduce.events(out)
    for name, s, e in sorted(ev["host"], key=lambda t: t[1]):
        print("host", name, s, e)
    for d, lines in ev["devices"].items():
        for key, items in lines.items():
            for name, s, e in items:
                print("device", d, key, name, s, e)
    print(trace_reduce.reduce(ev))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
