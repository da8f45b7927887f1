"""Record the small trace that benchmarks/tests/test_reduce_trace.py
reads (benchmarks/tests/data/tiny_tpu.xplane.pb). Run once on the chip:

    python3 benchmarks/tests/record_tiny_trace.py <output directory>

Three calls of one jitted program over a 512 x 512 array, under the same
profiler options as benchmarks/run.py's slice.
"""

import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.raise_error_on_start_failure = True
    jax.profiler.start_trace(out_dir + "/raw", profiler_options=opts)
    for _ in range(3):
        f(x).block_until_ready()
        time.sleep(0.03)
    jax.profiler.stop_trace()
    src = glob.glob(out_dir + "/raw/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out_dir + "/tiny_tpu.xplane.pb")
    shutil.rmtree(out_dir + "/raw")
    print(jax.devices()[0].device_kind, src)


if __name__ == "__main__":
    main(sys.argv[1])
