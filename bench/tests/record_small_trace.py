"""Record the small chip trace that ``test_xplane.py`` reads.

    python bench/tests/record_small_trace.py <out.xplane.pb>

Run on a machine with a TPU: two small jitted programs (``route_pass``,
``student_step``) inside ``bench.window``/``bench.tick`` host spans, with
host sleeps between them so that the trace has idle gaps.
"""
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    @jax.jit
    def route_pass(w, x):
        return jnp.tanh(x @ w) @ w.T

    @jax.jit
    def student_step(w, x):
        return w - 1e-3 * jax.grad(lambda v: jnp.sum(jnp.tanh(x @ v)))(w)

    w = jnp.ones((512, 512), jnp.float32) / 512
    x = jnp.ones((256, 512), jnp.float32)
    jax.block_until_ready((route_pass(w, x), student_step(w, x)))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.tick"):
                    y = route_pass(w, x)
                    w = student_step(w, y)
                    jax.block_until_ready(w)
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        Path(out).write_bytes(path.read_bytes())


if __name__ == "__main__":
    main(sys.argv[1])
