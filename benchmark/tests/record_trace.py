"""Record the small trace the tests read (`tiny_tpu.xplane.pb`): a few
executions of one small jitted program on the chip, each under a host
annotation, with a pause between two of them. Run on the chip:

    chiprun -- python3 benchmark/tests/record_trace.py chiprun_out/tiny
"""
import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> int:
    @jax.jit
    def tiny_step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((256, 256), jnp.bfloat16)
    tiny_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench/step"):
            tiny_step(x).block_until_ready()
        if i == 1:
            with jax.profiler.TraceAnnotation("bench/pause"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(out_dir + "/plugins/profile/*/*.xplane.pb")[-1]
    shutil.copy(src, out_dir + "/tiny_tpu.xplane.pb")
    print(src)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
