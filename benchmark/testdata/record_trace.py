"""How ``small_tpu.xplane.pb`` was recorded (on one v5e chip, PR 23): three calls of a small
jitted program with a matmul, an elementwise pass and a reduction, host and Python tracers off
so that the file stays small. ``python3 benchmark/testdata/record_trace.py <out_dir>``."""

import glob
import os
import shutil
import sys


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_step(x, w):
        return jnp.tanh(x @ w).sum(axis=-1)

    x, w = jnp.ones((512, 1024), jnp.bfloat16), jnp.ones((1024, 1024), jnp.bfloat16)
    small_step(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 0, 0
    tmp = os.path.join(out_dir, "tmp_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        small_step(x, w).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, os.path.join(out_dir, "small_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print(jax.devices()[0].device_kind, os.path.getsize(os.path.join(out_dir, "small_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
