"""Record the small trace that ``test_bench_trace.py`` reads.

    python3 tests/bench/record_trace.py <out_dir>

On one TPU chip: inside a ``bench.window`` span, five rounds of a
``bench.pump`` span, a ``bench.step`` span around a jitted 2048-square
bf16 matrix product waited for on the host, and a ``bench.wait`` span
around a 20 ms sleep.  The device is busy in the steps only, so the
longest idle time lies under ``bench.wait``.
"""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    f = jax.jit(lambda x: (x @ x) * 0.5)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.pump"):
                time.sleep(0.001)
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    print(sorted(str(p) for p in Path(out).rglob("*.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
