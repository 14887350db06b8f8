"""Record the small trace that the tests of `scope_reduce.py` read
(`scoped_tpu.xplane.pb`): four executions of one small jitted training
step on the chip, with two scopes, one nested in the other, under
`value_and_grad`, and an update and a sort outside any scope. Run on the chip:

    chiprun -- python3 benchmark/tests/record_scoped_trace.py chiprun_out/scoped
"""
import glob
import shutil
import sys

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    def loss(w, x):
        with jax.named_scope("dl4j.outer"):
            h = jnp.tanh(x @ w)
            with jax.named_scope("dl4j.inner"):
                p = jax.nn.softmax(h.astype(jnp.float32), axis=-1)
            y = p.astype(w.dtype) @ w
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    @jax.jit
    def scoped_step(w, x):
        value, grad = jax.value_and_grad(loss)(w, x)
        # no scope on either: the update (which XLA may fuse into the
        # matmul that makes `grad`) and a sort, which fuses with nothing
        return value, w - 0.5 * grad, jnp.sort(x[0].astype(jnp.float32))

    w = jnp.eye(512, dtype=jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.bfloat16)
    jax.block_until_ready(scoped_step(w, x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench/step"):
            value, w, _ = scoped_step(w, x)
            jax.block_until_ready(w)
    jax.profiler.stop_trace()
    src = glob.glob(out_dir + "/plugins/profile/*/*.xplane.pb")[-1]
    shutil.copy(src, out_dir + "/scoped_tpu.xplane.pb")
    print(src)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
