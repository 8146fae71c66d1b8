"""trace.py on a small trace recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import trace


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def f(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    for _ in range(4):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.find_xplane(d)


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_reduce_cpu_trace(cpu_trace):
    red = trace.reduce_trace(cpu_trace, layout=trace.CPU, window_s=None)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    names = [n for n, _ in red["top_ops"]]
    assert any("dot" in n for n in names), names
    assert all(s > 0 for _, s in red["top_ops"])
    assert len(red["idle_gaps"]) >= 1
    assert all(isinstance(n, str) and g > 0 for n, g in red["idle_gaps"])


def test_window_bounds_idle(cpu_trace):
    red = trace.reduce_trace(cpu_trace, layout=trace.CPU, window_s=10.0)
    assert red["window_s"] == 10.0
    assert red["busy_s"] < 10.0


def test_window_holds_every_traced_event(cpu_trace):
    """A caller's window read before the last traced work ended still
    holds all of it: busy time never exceeds the window."""
    full = trace.reduce_trace(cpu_trace, layout=trace.CPU, window_s=None)
    red = trace.reduce_trace(cpu_trace, layout=trace.CPU, window_s=1e-9)
    assert red["window_s"] == full["window_s"]
    assert 0 < red["busy_s"] <= red["window_s"]


def test_tpu_layout_finds_no_device_on_cpu(cpu_trace):
    red = trace.reduce_trace(cpu_trace, layout=trace.TPU)
    assert red["devices"] == 0 and red["busy_s"] == 0.0
