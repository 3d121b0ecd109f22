"""The trace reduction on a trace recorded on a TPU v5e: two jitted
programs, ``f`` (a 2048x2048 bf16 matmul) and ``g`` (an elementwise sum),
each run three times inside ``TraceAnnotation`` spans ``bench.f`` and
``bench.g``, 10 ms apart."""
from pathlib import Path

import pytest

from bench.trace_reduce import breakdown, reduce_trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "tpu_v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return reduce_trace(str(TRACE))


def test_busy_and_window(red):
    assert red.n_chips == 1
    assert red.window_s == pytest.approx(0.320008192)
    # six short programs in a third of a second: busy well under 1 ms
    assert 0.0002 < red.busy_s < 0.001
    assert red.busy_s / red.window_s < 0.01


def test_runs_attributed_to_enqueuing_span(red):
    # the device clock runs about a millisecond behind the host's, so the
    # device start of each run lies before its span; the enqueue does not
    assert [r.span for r in red.runs] == ["f", "g"] * 3
    f = [r for r in red.runs if r.span == "f"]
    assert len({r.module for r in f}) == 1
    assert all(r.start_ns < r.span_start_ns for r in f)
    assert red.device_seconds("f") == pytest.approx(
        (102486 + 102347 + 102531) / 1e9)
    assert red.device_seconds("g", "jit__lambda") == pytest.approx(
        (13347 + 13149 + 13206) / 1e9)


def test_breakdown(red):
    b = breakdown(red)
    assert b["device_ops"][0][0] == "fusion"
    assert {k for k, _ in b["idle_gaps"]} <= {"f", "g", "host.other"}
    assert sum(v for _, v in b["idle_gaps"]) <= red.window_s - red.busy_s
