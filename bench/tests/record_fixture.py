"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_fixture.py <out.xplane.pb>

Runs one small jitted program five times inside ``bench.step_once``
annotations, with a 20 ms ``bench.client_wait`` sleep after each, all in a
``bench.traced_window``; needs a TPU.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

CALLS = 5
WAIT_S = 0.02


def fixture_step(x):
    return jnp.tanh(x @ x) @ x


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    step = jax.jit(fixture_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation("bench.step_once"):
                step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.client_wait"):
                time.sleep(WAIT_S)
    jax.profiler.stop_trace()
    src = sorted(Path(d).rglob("*.xplane.pb"))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(d)
    print(out, Path(out).stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
