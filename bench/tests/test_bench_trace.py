"""The trace reduction, on small traces recorded on a TPU v5e."""
import pytest

import benchkit

trace = benchkit.load_module("trace", "bench_trace_under_test")


@pytest.fixture(scope="module")
def sweep():
    return trace.reduce(benchkit.chip_trace("sweep"))


@pytest.fixture(scope="module")
def serve():
    return trace.reduce(benchkit.chip_trace("serve"))


def test_window_busy_and_idle(sweep, serve):
    for s in (sweep, serve):
        assert [d.name for d in s.devices] == ["/device:TPU:0"]
        assert 0.5 < s.window_s < 2.0
        assert 0 < s.busy_s < s.window_s
        assert 0 < s.idle_share < 1
        gaps = sum(b - a for d in s.devices for a, b in d.gaps) * 1e-9
        assert gaps + s.busy_s == pytest.approx(s.window_s, rel=1e-6)


def test_programs_and_kernels(sweep, serve):
    # the sweep's fit runs the Mosaic kernel (a custom-call inside its
    # program), the assign runs the reference lowering (no kernel)
    fit = sweep.program_s("jit_fit_scan_padded")
    assert 0 < sweep.kernel_s("jit_fit_scan_padded") <= fit
    # the only other custom-call is a few nanoseconds inside the assign
    assert sweep.kernel_s("") == pytest.approx(sweep.kernel_s("jit_fit_scan_padded"), rel=1e-3)
    assert sweep.program_s("jit_assign_padded") > 0
    assert serve.program_s("jit_assign_padded") > 0
    assert sweep.program_s("no_such_program") == 0.0


def test_breakdown_names_ops_and_what_the_host_did(sweep, serve):
    for s, spans in ((sweep, {"bench.explore"}), (serve, {"bench.submit", "bench.flush"})):
        bd = s.breakdown()
        assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
        secs = [v for _, v in bd["device_ops"]]
        assert secs == sorted(secs, reverse=True) and secs[0] > 0
        labels = {k for k, _ in bd["idle_gaps"]}
        assert labels <= spans | {"bench.idle"} and labels & spans
        idle = sum(v for _, v in bd["idle_gaps"])
        assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert any("custom-call kernel" in n for n, _ in sweep.breakdown()["device_ops"])


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError, match="no 'bench.nothing' span"):
        trace.reduce(benchkit.chip_trace("sweep"), window="bench.nothing")


def test_helpers():
    assert trace._union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert trace._program("jit_assign_padded(1234)") == "jit_assign_padded"
    assert trace._op("%fusion.8 = f32[8]{0} fusion(f32[8] %a)") == "fusion"
    assert trace._op("%closed_call.5 = f32[2] custom-call(f32[2] %x)") == \
        "closed_call (custom-call kernel)"
    intervals = [(0, 10, "a"), (20, 30, "b")]
    assert trace._containing(intervals, 25) == "b"
    assert trace._containing(intervals, 15) is None
