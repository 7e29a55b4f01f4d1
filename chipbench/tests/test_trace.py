"""The trace reduction on a small trace recorded here on the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from lib import trace


def test_recorded_trace_reduces_to_busy_time_programs_and_gaps(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
        with TraceAnnotation("bench.wait_arrival"):
            time.sleep(0.05)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    red = trace.reduce(trace.load(trace.find_xplane(str(tmp_path)),
                                  "/host:CPU"))
    assert 0.05 < red["window_s"] < 5
    assert 0 < red["busy_s"] < red["window_s"]
    assert any("lambda" in k for k in red["programs"])
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    longest = red["idle_gaps"][0]
    assert longest[0] == "bench.wait_arrival"
    assert longest[1] == pytest.approx(0.05, abs=0.03)
