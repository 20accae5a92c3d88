"""GL113 positive: an unstopped profiler trace (buffers forever, the
.xplane.pb never flushes — a run's profiling silently lost),
and profiler trace control from inside jit-traced code (runs once at
trace time, so the "profiled" region covers tracing, not execution)."""
import jax
import jax.numpy as jnp

from pytorch_multiprocessing_distributed_tpu.utils.profiler import trace


def capture_forever(logdir):
    jax.profiler.start_trace(logdir)               # <- GL113
    return jnp.zeros(())


@jax.jit
def step(x, logdir):
    with trace(logdir):                            # <- GL113
        y = jnp.sum(x)
    jax.profiler.start_trace(logdir)               # <- GL113
    return y
