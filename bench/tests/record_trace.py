"""Records the small TPU trace ``test_trace.py`` reduces.

    python bench/tests/record_trace.py <output.xplane.pb>

Run on one chip: three jitted programs inside a ``step`` span (each fed a
fresh 16 MB host array), a 20 ms sleep inside ``await_arrival``, a sort
inside ``submit``; host tracing at level 1, no Python tracer.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x * 2.0).sum(axis=1))
    g = jax.jit(lambda x: jnp.sort(x, axis=-1)[:, :8])
    x = np.random.default_rng(0).normal(size=(1024, 4096)).astype(np.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("step"):
        for i in range(3):
            f(x + i).block_until_ready()
    with jax.profiler.TraceAnnotation("await_arrival"):
        time.sleep(0.02)
    with jax.profiler.TraceAnnotation("submit"):
        g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    shutil.copy(path, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
