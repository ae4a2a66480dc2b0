"""Record `cpu.xplane.pb`, the small CPU trace that tests the trace
reduction: a jitted matmul run three times under the harness's
annotations, with host-only stretches of known length between.

    JAX_PLATFORMS=cpu python3 bench/testdata/record_trace.py
"""

import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("tick"):
                with TraceAnnotation("decode_round"):
                    f(x).block_until_ready()
            with TraceAnnotation("wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "cpu.xplane.pb"))
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
