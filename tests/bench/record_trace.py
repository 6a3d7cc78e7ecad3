"""Record the small profiler trace ``tests/bench/data/small.xplane.pb``
that ``test_bench_trace_reduce.py`` reduces, on the chip:

    python3 tests/bench/record_trace.py <output directory>

Three rounds of: a jitted matmul inside ``bench.step``, then 20 ms of
host sleep inside ``bench.wait`` (the device idles), then a short
``bench.host`` span.
"""
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = Path(out) / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.001)
    jax.profiler.stop_trace()
    src = sorted(tmp.rglob("*.xplane.pb"))[-1]
    shutil.copy(src, Path(out) / "small.xplane.pb")
    print(src, src.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
