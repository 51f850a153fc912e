"""The trace reduction: interval arithmetic on hand-made intervals, and a
small trace recorded here on the CPU (its XLA ops stand in for device
operations)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from bench import trace


def test_union_covered_gaps():
    merged = trace.union([(5.0, 6.0), (0.0, 1.0), (0.5, 2.0), (2.0, 3.0)])
    assert merged == [(0.0, 3.0), (5.0, 6.0)]
    assert trace.covered(merged, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.covered(merged, 2.5, 5.5) == pytest.approx(1.0)
    assert trace.gaps(merged, -1.0, 7.0) == [(-1.0, 0.0), (3.0, 5.0),
                                             (6.0, 7.0)]
    assert trace.gaps(merged, 1.0, 2.0) == []


def test_trace_object_arithmetic():
    busy = {"d0": trace.union([(1.0, 2.0), (3.0, 4.0)]),
            "d1": trace.union([(1.0, 4.0)])}
    t = trace.Trace(window=(0.0, 5.0), busy=busy,
                    op_s={"_kernel.1": 1.5, "_kernel.2": 0.5, "copy": 2.0},
                    spans={"bench.update": [(0.0, 2.0)]},
                    host_events=[("bench.window", 0.0, 5.0),
                                 ("bench.update", 0.0, 2.0),
                                 ("plan_delta", 0.0, 1.0)])
    assert t.window_s == 5.0
    assert t.busy_s == pytest.approx((2.0 + 3.0) / 2)
    assert t.device_idle_in(0.0, 2.0) == pytest.approx((1.0 + 1.0) / 2)
    assert t.kernel_s(("_kernel",)) == pytest.approx(2.0 / 2)
    b = t.breakdown()
    assert b["device_ops"][0] == ["copy", 2.0]
    # d0's longest idle gap is [0, 1]: the innermost host event over it
    assert b["idle_gaps"][0] == ["plan_delta", 1.0]


def test_reduce_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.update"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    # on the CPU the ops run on the host plane's client thread
    t = trace.reduce(path, device_prefix="/host:CPU",
                     op_line="tf_XLAPjRtCpuClient")
    assert len(t.spans["bench.update"]) == 3
    assert 0 < t.busy_s <= t.window_s
    idle = 1 - t.busy_s / t.window_s
    assert 0 <= idle < 1
    total = sum(t.op_s.values()) / len(t.busy)
    assert t.kernel_s(("",)) == pytest.approx(total)
    assert 0 < t.kernel_s(("dot",)) <= total
    b = t.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["idle_gaps"])


def test_reduce_refuses_a_trace_without_device(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        np.zeros(4).sum()
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="no /device:TPU"):
        trace.reduce(trace.find_xplane(str(tmp_path)))


def test_self_times_of_nested_events():
    evs = [("%while.1", 0.0, 10.0), ("%cond.2", 1.0, 6.0),
           ("%tpu_custom_call.3", 1.5, 3.5), ("%tpu_custom_call.3", 4.0, 5.0),
           ("%fusion.4", 7.0, 8.0), ("%copy.5", 11.0, 12.0)]
    st = trace.self_times(evs)
    assert st == pytest.approx({"%while.1": 4.0, "%cond.2": 2.0,
                                "%tpu_custom_call.3": 3.0, "%fusion.4": 1.0,
                                "%copy.5": 1.0})
    assert sum(st.values()) == pytest.approx(
        trace.covered(trace.union([(s, e) for _, s, e in evs]), 0, 12))


def test_reduce_recorded_tpu_trace_names():
    """A TPU trace names ops by HLO instruction; the reduction keeps the
    instruction name and the tile SpMV reader's prefix matches it."""
    from bench.kernels import SPMV_KERNELS
    evs = [("%while.74", 0.0, 4.0), ("%tpu_custom_call.71", 0.5, 1.5),
           ("%tpu_custom_call.66", 2.0, 3.5)]
    t = trace.Trace(window=(0.0, 5.0),
                    busy={"/device:TPU:0": trace.union(
                        [(s, e) for _, s, e in evs])},
                    op_s=trace.self_times(evs), spans={}, host_events=[])
    assert t.kernel_s(SPMV_KERNELS) == pytest.approx(2.5)
    assert t.busy_s == pytest.approx(4.0)
