"""Record the small chip trace that holds the program's own spans.

    python chipbench/tests/record_span_trace.py <out_dir>

Run on a TPU host, with ``src`` on the path.  Three jobs inside the
harness's ``chipbench.window`` span, each a ``chipbench.job`` span holding
the spans the program records through its Telemetry at level ``trace``:
``engine.upload`` (a 64 MiB input to the device), ``engine.loop`` (about
20 ms of jitted matmuls, until done) and ``engine.fetch`` (the result back
and widened, with a host sleep); a sleep of the job's own follows, then a
``chipbench.host_wait``.  Every step lasts tens of milliseconds: the
device's timestamps may sit a few milliseconds off the host's.  Copy the
``.xplane.pb`` it writes to ``chipbench/tests/data/spans.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Telemetry


def job(x):
    return jax.lax.fori_loop(0, 20, lambda i, y: jnp.tanh(y @ x), x).sum(axis=0)


def main(out_dir: str) -> None:
    tel = Telemetry("trace")
    f = jax.jit(job)
    x = np.full((4096, 4096), 1e-3, np.float32)
    f(jnp.asarray(x)).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.job"):
                with tel.trace_span("engine.upload"):
                    xd = jax.device_put(x).block_until_ready()
                with tel.trace_span("engine.loop"):
                    y = f(xd).block_until_ready()
                with tel.trace_span("engine.fetch"):
                    np.asarray(y, np.float64)
                    time.sleep(0.02)
                time.sleep(0.01)
            with jax.profiler.TraceAnnotation("chipbench.host_wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
