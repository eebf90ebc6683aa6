#!/usr/bin/env python3
"""Record the small TPU trace ``test_tracereduce.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb>

Inside a ``bench.window`` annotation: a matrix product, a 50 ms sleep
under ``bench.sleep`` (an idle gap of known cause), a second product;
then a ``bench.burst.mm`` annotation around three products.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str):
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready(mm(a))
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(mm(a))
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        jax.block_until_ready(mm(a))
    with jax.profiler.TraceAnnotation("bench.burst.mm"):
        for _ in range(3):
            y = mm(a)
        jax.block_until_ready(y)
    jax.profiler.stop_trace()
    (f,) = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    shutil.copy(f, out)


if __name__ == "__main__":
    main(sys.argv[1])
