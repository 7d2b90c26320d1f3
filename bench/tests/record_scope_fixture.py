"""Record the small TPU trace with named scopes that
``test_trace_scopes.py`` reads.

    python bench/tests/record_scope_fixture.py <out.xplane.pb>

Runs a jitted ``scoped_step`` five times: a ``scan`` of four layers whose
body holds two named scopes (``alpha``, ``beta``, a matmul each), and an
unscoped sum after it.  Each run sits in the program's own host phases
(``serving/telemetry.py`` ``Tracer.phase``: ``repro.step`` over
``repro.dispatch``, ``repro.sync`` and a 20 ms ``repro.post`` sleep),
inside ``bench.step_once``, all in a ``bench.traced_window``; needs a TPU.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src")]

from repro.serving.telemetry import Tracer  # noqa: E402

CALLS = 5
LAYERS = 4
WAIT_S = 0.02


def scoped_step(x, ws):
    def body(c, w):
        with jax.named_scope("alpha"):
            c = jnp.tanh(c @ w)
        # keeps the compiler from fusing the two scopes into one op
        c = jax.lax.optimization_barrier(c)
        with jax.named_scope("beta"):
            c = jnp.sin(c @ w.T) * 0.5
        return c, None

    c, _ = jax.lax.scan(body, x, ws)
    return c.astype(jnp.float32).sum()


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    step = jax.jit(scoped_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    ws = jnp.full((LAYERS, 1024, 1024), 1e-3, jnp.bfloat16)
    step(x, ws).block_until_ready()
    tracer = Tracer()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for s in range(CALLS):
            with jax.profiler.TraceAnnotation("bench.step_once"), \
                    tracer.phase("step", s):
                with tracer.phase("dispatch", s):
                    y = step(x, ws)
                with tracer.phase("sync", s):
                    y.block_until_ready()
                with tracer.phase("post", s):
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
