"""How ``scoped_tpu.xplane.pb`` was recorded (on one v5e chip, PR 39): two calls of a small jitted
program whose work lies inside containers, as a hybrid's prefill does: a ``lax.switch`` inside a
``lax.scan`` with a named scope in each branch (``attn``, ``moe``) and a sub-scope nested in one
(``moe.blocks``), then a Pallas call with a ``name=`` under a third scope (``mlp``). Host and
Python tracers off, as the harness traces, so that the file stays small.
``python3 benchmark/testdata/record_scoped_trace.py <out_dir>``."""

import glob
import os
import shutil
import sys


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def attn_branch(x, w):
        with jax.named_scope("attn"):
            return jnp.tanh(x @ w)

    def moe_branch(x, w):
        with jax.named_scope("moe"):
            y = x * jnp.asarray(0.5, x.dtype)
            with jax.named_scope("moe.blocks"):
                y = y @ w
            return jax.nn.relu(y) + x

    @jax.jit
    def scoped_step(x, w, kinds):
        def body(x, kind):
            return jax.lax.switch(kind, [attn_branch, moe_branch], x, w), None

        x, _ = jax.lax.scan(body, x, kinds)
        with jax.named_scope("mlp"):
            return pl.pallas_call(double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name="scoped_double")(x)

    x, w = jnp.ones((512, 1024), jnp.bfloat16), jnp.full((1024, 1024), 1e-3, jnp.bfloat16)
    kinds = jnp.asarray([0, 1, 1, 0], jnp.int32)
    scoped_step(x, w, kinds).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 0, 0
    tmp = os.path.join(out_dir, "tmp_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(2):
        scoped_step(x, w, kinds).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, os.path.join(out_dir, "scoped_tpu.xplane.pb"))
    shutil.rmtree(tmp)
    print(jax.devices()[0].device_kind, os.path.getsize(os.path.join(out_dir, "scoped_tpu.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
