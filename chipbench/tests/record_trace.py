"""Record the small device trace that ``test_chipbench_trace.py`` reads.

Run on the chip (``python chipbench/tests/record_trace.py <out>``): three
runs of a small jitted step, with a benchmark annotation around each and
a host-side pause annotated as a save between the second and the third.
The trace is written under ``<out>`` (default ``.chipbench_run/small_trace``
in the checkout).
"""
import os
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def step(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("chipbench.step"):
            x = step(x)
        x.block_until_ready()
        if i == 1:
            with jax.profiler.TraceAnnotation("chipbench.save"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else
                  os.path.join(".chipbench_run", "small_trace")))
