"""How ``tests/data/tiny.xplane.pb`` was recorded (on the chip, PR 23):
three calls of one small jitted program under the JAX profiler.

    python benchmark/tests/record_tiny_trace.py chiprun_out/tiny_trace
"""

import glob
import os
import sys
import time


def main() -> None:
    import jax
    import jax.numpy as jnp
    out = sys.argv[1]

    @jax.jit
    def tiny_step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((512, 512), jnp.bfloat16)
    tiny_step(x).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        tiny_step(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    for path in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        if os.path.isfile(path):
            print(path, os.path.getsize(path))
    print(jax.devices())


if __name__ == "__main__":
    main()
