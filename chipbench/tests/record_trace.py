"""Record the small chip trace that the trace-reduction test reads.

    python chipbench/tests/record_trace.py <out_dir>

Run on a TPU host.  Three calls of a small jitted program inside the
harness's ``chipbench.window`` span, each in a ``chipbench.job`` span with
a host sleep between them, so the trace holds device operations, idle gaps
and the spans they are put down to.  Copy the ``.xplane.pb`` it writes to
``chipbench/tests/data/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.job"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.host_wait"):
                time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
