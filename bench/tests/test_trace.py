"""The trace reduction on a small recorded trace: three windows of a
jitted matmul inside `bench.route` spans, each followed by 20 ms of
host work inside a `bench.feedback` span (recorded on the CPU, whose
ops stand in for a device's in a rehearsal)."""
from pathlib import Path

import pytest

from bench.lib.tracing import Trace

FIXTURE = Path(__file__).parent / "data" / "trace_cpu_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return Trace.from_profile(ProfileData.from_file(str(FIXTURE)))


def test_spans_are_the_benchmarks(trace):
    names = [n for _, _, n in trace.spans]
    assert names.count("bench.route") == 3
    assert names.count("bench.feedback") == 3


def test_busy_is_a_part_of_the_window(trace):
    assert trace.ops
    assert 0 < trace.busy_s() < trace.window_s
    # three 20 ms sleeps leave the device idle for at least 60 ms
    assert trace.window_s - trace.busy_s() >= 0.06


def test_idle_gaps_are_named_by_the_open_span(trace):
    gaps = dict(trace.idle_gaps())
    assert gaps["bench.feedback"] >= 0.04
    assert max(gaps, key=gaps.get) == "bench.feedback"


def test_op_seconds_counts_matching_ops(trace):
    secs, n = trace.op_seconds(lambda name, st: "dot" in name)
    assert n >= 3 and secs > 0
    none, zero = trace.op_seconds(lambda name, st: False)
    assert (none, zero) == (0.0, 0)
    assert trace.top_ops(3) and len(trace.top_ops(3)) <= 3
