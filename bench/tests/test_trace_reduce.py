"""The trace reduction on a trace recorded on a TPU v5e
(``tools/record_trace.py``: the fused KD loss forward and backward and a
2048x2048 matmul, three times, inside ``bench.*`` spans)."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_kd_matmul.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(DATA)


def test_union_merges_overlaps():
    assert tr._union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]


def test_one_device_with_ops(trace):
    assert [d.index for d in trace.devices] == [0]
    assert len(trace.devices[0].ops) == 51


def test_busy_is_union_and_within_ops(trace):
    dev = trace.devices[0]
    total = sum(d for _, d, _ in dev.ops) * 1e-9
    busy = trace.busy_s()
    # three matmuls of ~114 us and three KD calls of ~6 us dominate
    assert 3e-4 < busy <= total
    iv = dev.busy_intervals()
    assert all(a[1] < b[0] for a, b in zip(iv, iv[1:]))


def test_kd_kernel_found(trace):
    def match(op):
        return "custom-call(" in op and "f32[64,400]" in op
    assert trace.op_count(match) == 3
    assert 0 < trace.op_time_s(match) < 1e-4


def test_top_ops_and_spans(trace):
    top = trace.top_ops(3)
    assert top[0][0] == "convolution_tanh_fusion"
    names = {n for _, _, n in trace.spans}
    assert {"bench.window", "bench.engine", "bench.hook"} <= names


def test_idle_gaps_named_by_span(trace):
    assert trace.idle_gaps(10) == []       # none of a millisecond
    gaps = trace.idle_gaps(10, min_gap_ns=5e5)
    assert len(gaps) == 2, gaps
    assert all(g[1] >= 5e-4 for g in gaps)
    assert all(g[0].startswith("bench.") or g[0] == "outside harness spans"
               for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_self_time_of_nested_ops():
    # a scan's while op [0, 100) holds two body ops; a later op stands alone
    ops = [(0, 100, "%while.1 = while"), (10, 30, "%fusion.2 = a"),
           (50, 40, "%fusion.3 = b"), (120, 5, "%copy.4 = c")]
    got = {k: round(v * 1e9, 6) for k, v in tr._self_times(ops)}
    assert got == {"%while.1 = while": 30, "%fusion.2 = a": 30,
                   "%fusion.3 = b": 40, "%copy.4 = c": 5}
    t = tr.Trace(devices=[tr.DeviceTrace(0, ops)], spans=[])
    assert t.top_ops(1)[0][0] == "fusion.3"
    assert round(t.busy_s() * 1e9, 6) == 105



def test_trace_start_on_the_wall_clock(trace):
    # recorded on 2026-10-18; 1e18 ns is September 2001
    assert trace.start_epoch_ns > 1e18


def test_host_spans_share_the_trace_clock(tmp_path):
    """A ``run.HostSpan`` on the host's wall clock lands where the
    profiler puts the same region (here on the CPU, whose host tracer is
    on for the comparison)."""
    import time

    import jax

    import run

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    mine = []
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    time.sleep(0.02)
    with run.HostSpan(mine, "bench.mine"), \
            jax.profiler.TraceAnnotation("bench.theirs"):
        time.sleep(0.02)
    jax.profiler.stop_trace()
    t = tr.load(str(next(tmp_path.glob("**/*.xplane.pb"))))
    theirs = [s for s in t.spans if s[2] == "bench.theirs"]
    t.add_host_spans(mine)
    mine = [s for s in t.spans if s[2] == "bench.mine"]
    assert len(theirs) == 1 and len(mine) == 1
    assert abs(mine[0][0] - theirs[0][0]) < 1e6           # under 1 ms
    assert abs(mine[0][1] - theirs[0][1]) < 1e6
