"""Record the small trace that ``test_trace_reduce.py`` checks the
reduction on: a few executions of two named programs with host spans
around them, on whatever accelerator this runs on (meant for the chip:
``chiprun -- python benchmarks/tests/record_trace.py chiprun_out/small``).
"""

import os
import shutil
import sys
import tempfile
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_matmul(a):
        return jnp.tanh(a @ a)

    @jax.jit
    def small_loop(a):
        return jax.lax.fori_loop(0, 8, lambda i, x: x * 1.0001 + 1.0, a)

    a = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready((small_matmul(a), small_loop(a)))
    tmp = tempfile.mkdtemp(prefix="small_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(small_matmul(a))
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
            jax.block_until_ready(small_loop(a))
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    for dirpath, _d, files in os.walk(tmp):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                shutil.copy(os.path.join(dirpath, fn),
                            os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
