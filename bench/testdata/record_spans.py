"""Record `spans.xplane.pb`, the small CPU trace that tests how the trace
reduction names idle gaps by the program's own spans: three ticks of a
jitted matmul under the harness's annotations and the program's spans
(`EngineTelemetry.span`), in which the host sleeps 20 ms inside
`decode.sample` with the device idle.

    JAX_PLATFORMS=cpu python3 bench/testdata/record_spans.py
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.obs import EngineTelemetry  # noqa: E402


def main():
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    tel = EngineTelemetry(attach=False, energy=False)
    tel.now = time.perf_counter
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("tick"), tel.span("step"):
                with tel.span("decode_round", "economy"), \
                        TraceAnnotation("decode_round"):
                    with tel.span("decode.dispatch"):
                        y = f(x)
                    with tel.span("decode.fetch"):
                        y.block_until_ready()
                    with tel.span("decode.sample"):
                        time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "spans.xplane.pb"))
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
