"""Record the small trace kept beside test_trace_reduce.py: a few steps
of a small jitted program on the chip, with the runner's host spans.  Run
by hand through the chip tool; writes chiprun_out/small_trace/."""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run as bench  # noqa: E402

out = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
                   "small_trace")
spans = bench.Spans()
step = jax.jit(lambda x, w: jnp.tanh(x @ w).astype(jnp.bfloat16))
x = jnp.ones((1024, 1024), jnp.bfloat16)
w = jnp.ones((1024, 1024), jnp.bfloat16)
step(x, w).block_until_ready()
tracer = bench.Tracer(True, os.path.join(out, "trace"))
tracer.start()
for _ in range(4):
    with spans.span("data_wait"):
        time.sleep(0.002)
    with spans.span("dispatch"):
        x = step(x, w)
    with spans.span("device_wait"):
        x.block_until_ready()
tracer.stop()
pb = glob.glob(os.path.join(out, "trace", "plugins", "profile", "*",
                            "*.xplane.pb"))[0]
shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
print(os.path.getsize(pb), "bytes")
