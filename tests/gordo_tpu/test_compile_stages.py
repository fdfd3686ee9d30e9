"""The compile path by stage (``util/xla_cache._count_compiles``): jax's own
trace, lowering and backend-compile events become
``gordo_build_xla_compile_seconds_total{stage}``; a nested trace counts
once, on the ``time.time()`` clock; a program read back from the persistent
cache is a ``load``."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.util import xla_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
STAGES = ("trace", "lower", "compile", "load")


def _seconds():
    return {s: metric_catalog.XLA_COMPILE_SECONDS.value(stage=s) for s in STAGES}


@pytest.fixture
def spans():
    """Every time span jax reports while the test runs, beside the listener."""
    xla_cache._count_compiles()
    seen = []

    def spy(event, start, end, **_kwargs):
        seen.append((event, start, end))

    jax.monitoring.register_event_time_span_listener(spy)
    yield seen
    jax.monitoring.unregister_event_time_span_listener(spy)


def test_a_jit_inside_a_jit_counts_its_trace_once(spans):
    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)

    def outer_body(x):
        return jnp.tanh(inner(x)) + inner(x * 3.0)

    outer = jax.jit(outer_body)
    x = jnp.ones((5, 3))
    before = _seconds()
    t0 = time.time()
    outer(x).block_until_ready()
    t1 = time.time()
    after = _seconds()
    traces = [
        (start, end) for event, start, end in spans
        if event == TRACE_EVENT and end > t0  # the input's own came before
    ]
    # every trace of the call is on the clock setup_s is read on
    assert traces and all(t0 <= start <= end <= t1 for start, end in traces)
    first, last = min(s for s, _ in traces), max(e for _, e in traces)
    # the inner jit's trace (and any jnp function's own) ran inside the
    # outer one's
    assert len(traces) >= 2
    assert max(traces, key=lambda span: span[1] - span[0]) == (first, last)
    counted = after["trace"] - before["trace"]
    assert counted == pytest.approx(last - first, abs=1e-9)
    assert counted < sum(end - start for start, end in traces)
    assert after["lower"] > before["lower"]
    assert after["compile"] + after["load"] > before["compile"] + before["load"]
    assert sum(after[s] - before[s] for s in STAGES) <= t1 - t0


SECOND_PROCESS = """
import json
import jax
import jax.numpy as jnp
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.util.xla_cache import setup_persistent_xla_cache

setup_persistent_xla_cache()
# keep every program, however fast it compiled (the CPU keeps jax's threshold)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({
    "seconds": {s: metric_catalog.XLA_COMPILE_SECONDS.value(stage=s)
                for s in ("trace", "lower", "compile", "load")},
    "loaded": metric_catalog.XLA_COMPILES.value(source="persistent_cache"),
    "compiled": metric_catalog.XLA_COMPILES.value(source="compiled"),
}))
"""


def test_a_program_from_the_persistent_cache_is_a_load(tmp_path):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
    )
    runs = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", SECOND_PROCESS], env=env, cwd=str(tmp_path),
            capture_output=True, text=True, timeout=300,
        )
        assert done.returncode == 0, done.stderr[-2000:]
        runs.append(json.loads(done.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["compiled"] >= 1 and cold["loaded"] == 0
    assert cold["seconds"]["compile"] > 0 and cold["seconds"]["load"] == 0
    # the same function, a second process: read back, not compiled
    assert warm["loaded"] >= 1 and warm["compiled"] == 0
    assert warm["seconds"]["load"] > 0 and warm["seconds"]["compile"] == 0
    # tracing and lowering happen in every process, cache or not
    assert warm["seconds"]["trace"] > 0 and warm["seconds"]["lower"] > 0
