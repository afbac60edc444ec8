"""The trace reduction on a fixed event list, and on a trace recorded on
the CPU for the host spans."""
import pytest

from chipbench import trace
from chipbench.trace import Event, Span

PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}

# two device ops overlap on [0.5, 1]; the device is idle on [2, 3] and
# [4, 5] of the window [0, 5]
EVENTS = [
    Event("fusion.1", 0.0, 1.0, "fusion.1", "/device:TPU:0"),
    Event("flash_attention.2", 0.5, 2.0, "flash_attention.2 kernel",
          "/device:TPU:0"),
    Event("flash_attention.3", 3.0, 4.0, "flash_attention.3 kernel",
          "/device:TPU:0"),
    Event("fusion.9", 6.0, 7.0, "fusion.9", "/device:TPU:0"),  # outside
]
SPANS = [
    Span("chipbench.window", 0.0, 5.0),
    Span("chipbench.dispatch.hot", 1.9, 2.2),
    Span("chipbench.wait", 2.1, 3.1),
]


def test_busy_is_the_union_of_intervals():
    assert trace.intervals(EVENTS, 0.0, 5.0) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.busy(EVENTS, 0.0, 5.0) == pytest.approx(3.0)
    assert trace.busy(EVENTS, 0.5, 3.5) == pytest.approx(2.0)


def test_busy_averages_over_devices():
    other = [e._replace(plane="/device:TPU:1") for e in EVENTS[:1]]
    assert trace.busy(EVENTS + other, 0.0, 5.0) == pytest.approx(2.0)


def test_kernel_sums_matching_ops_inside_the_window():
    assert trace.kernel(EVENTS, r"flash_attention", 0.0, 5.0) == (
        2, pytest.approx(2.5))
    assert trace.kernel(EVENTS, r"fusion", 0.0, 5.0) == (1, 1.0)
    assert trace.kernel(EVENTS, r"nothing", 0.0, 5.0) == (0, 0.0)


def test_gaps_are_named_by_the_span_that_covers_most_of_them():
    gaps = trace.idle_gaps(EVENTS, SPANS, 0.0, 5.0)
    assert [g for g, _ in gaps] == ["chipbench.wait", trace.NO_SPAN]
    assert [s for _, s in gaps] == [pytest.approx(1.0), pytest.approx(1.0)]


def test_top_ops_by_device_time():
    assert trace.top_ops(EVENTS, 0.0, 5.0, n=2) == [
        ("flash_attention.2", 1.5), ("fusion.1", 1.0)]


def test_roofline_arithmetic():
    # 200 FLOPs at 100/s take 2 s, 5 bytes at 10/s take 0.5 s: FLOP-bound
    assert trace.least_seconds(200.0, 5.0, PEAKS) == (2.0, "flops")
    assert trace.least_seconds(10.0, 50.0, PEAKS) == (5.0, "bytes")
    # 3 calls of 0.5 s of least time in 6 s of kernel time: 25%
    assert trace.roofline_share(3, 0.5, 6.0) == pytest.approx(25.0)
    assert trace.roofline_share(0, 0.5, 6.0) is None


def test_window_and_spans_from_a_recorded_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    f = jax.jit(lambda x: x * 2.0)
    x = jax.numpy.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.dispatch.hot"):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    _, spans = trace.read_xplane(trace.find_xplane(str(tmp_path)))
    t0, t1 = trace.window(spans, "chipbench.window")
    inner = [s for s in spans if s.name == "chipbench.dispatch.hot"]
    assert len(inner) == 1 and t0 <= inner[0].start <= inner[0].end <= t1
