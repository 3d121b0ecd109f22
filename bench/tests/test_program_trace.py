"""Program spans against device idle time (``bench/program_trace.py``), and
the readers of the per-layer metrics that read the program's stamps and
counters, on fabricated contexts."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.program_trace import (NONE, ProgramSpan, ProgramTrace, attribute,
                                 decode_host_ms, idle_loop_share, nest,
                                 readback_ms, reduce_program)
from bench.trace_reduce import breakdown, reduce_trace

BENCH = Path(__file__).resolve().parents[1]
TRACE = BENCH / "testdata" / "tpu_v5e_small.xplane.pb"
MS = 1_000_000


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_small_trace_reads_as_the_harness_reads_it():
    red = reduce_trace(str(TRACE))
    pt = reduce_program(str(TRACE))
    assert red.window_s == pytest.approx(0.320008192)
    assert [r.span for r in red.runs] == ["f", "g"] * 3
    assert breakdown(red)["device_ops"][0][0] == "fusion"
    assert pt.window_s == red.window_s
    assert pt.busy_s == pytest.approx(red.busy_s)
    gaps = sum(e - s for s, e in pt.gaps) / 1e9
    assert gaps == pytest.approx(sum(red.idle_by_span.values()))
    assert pt.edge_s > 0
    assert pt.busy_s + gaps + pt.edge_s == pytest.approx(pt.window_s)
    # the device clock runs about a millisecond behind the host's
    assert -2 * MS < pt.skew_ns < 0
    # the trace predates the program's spans: everything reads nothing
    assert pt.spans == [] and pt.idle_by_span() == {NONE: gaps}
    assert readback_ms(pt) is None and decode_host_ms(pt) is None
    assert idle_loop_share(pt) is None


def _synthetic() -> ProgramTrace:
    """One loop iteration (0-100 ms) holding a decode step (10-40) with its
    launch (12-20) and a prefill chunk (50-90) with its readback (60-80);
    a second thread's span, and idle gaps at midpoints 5, 15, 30, 45, 70,
    95 and 150 ms."""
    s = [ProgramSpan("loop.iteration", 0, 100 * MS, "engine"),
         ProgramSpan("decode.step", 10 * MS, 40 * MS, "engine"),
         ProgramSpan("decode.launch", 12 * MS, 20 * MS, "engine"),
         ProgramSpan("prefill.chunk", 50 * MS, 90 * MS, "engine"),
         ProgramSpan("prefill.readback", 60 * MS, 80 * MS, "engine"),
         ProgramSpan("other", 140 * MS, 160 * MS, "client")]
    gaps = [(4, 6), (14, 16), (29, 31), (44, 46), (68, 72), (94, 96),
            (149, 151)]
    pt = ProgramTrace(window_s=0.2, busy_s=0.18, spans=s,
                      gaps=[(a * MS, b * MS) for a, b in gaps])
    nest(pt.spans)
    attribute(pt)
    return pt


def test_program_spans_receive_the_gaps_their_midpoints_fall_in():
    pt = _synthetic()
    assert [h.parent for h in pt.spans] == [-1, 0, 1, 0, 3, -1]
    assert pt.gap_span == [0, 2, 1, 0, 4, 0, 5]
    idle = pt.idle_by_span()
    assert idle == pytest.approx({"loop.iteration": 0.006,
                                  "decode.launch": 0.002,
                                  "decode.step": 0.002,
                                  "prefill.readback": 0.004,
                                  "other": 0.002})
    assert pt.idle_inside("decode.step") == pytest.approx({1: 0.004})
    assert decode_host_ms(pt) == pytest.approx(4.0)
    assert idle_loop_share(pt) == pytest.approx(3.0)
    assert readback_ms(pt) == pytest.approx(20.0)


def test_deep_nesting_finds_the_outer_span():
    """A gap late in a long iteration, after many finished children, goes
    to the iteration and not to ``none``."""
    kids = [ProgramSpan("prefill.compute", (2 * i + 1) * MS,
                        (2 * i + 2) * MS, "engine") for i in range(200)]
    pt = ProgramTrace(window_s=1.0, busy_s=0.5,
                      spans=[ProgramSpan("loop.iteration", 0, 1000 * MS,
                                         "engine")] + kids,
                      gaps=[(500 * MS, 510 * MS)])
    nest(pt.spans)
    attribute(pt)
    assert pt.idle_by_span() == pytest.approx({"loop.iteration": 0.01})
    assert all(h.parent == 0 for h in kids)


def _ctx(outputs: dict, c0: dict, c1: dict):
    recs = [{"req_id": rid} for rid in outputs]
    return SimpleNamespace(window=SimpleNamespace(
        loop=SimpleNamespace(outputs=outputs)), records=recs,
        counters0={"prefill": c0}, counters1={"prefill": c1})


def _out(**kw):
    base = dict(token_t=[], t_submit=None, t_first_chunk=None,
                prefill_busy_s=0.0)
    base.update(kw)
    return SimpleNamespace(**base)


KEYS = ("h2d_bytes", "d2h_bytes", "put_bytes", "stage_bytes")


@pytest.mark.parametrize("name,value", [
    ("loop.queue_wait_ms", 150.0),
    ("loop.prefill_wait_ms", 200.0),
    ("kv.moved_mib", 5.0),
])
def test_readers_read_the_program(name, value):
    outs = {1: _out(t_submit=10.0, t_first_chunk=10.1, token_t=[11.0, 11.1],
                    prefill_busy_s=0.6),
            2: _out(t_submit=20.0, t_first_chunk=20.2, token_t=[21.0],
                    prefill_busy_s=0.7)}
    c0 = dict(requests=3, **{k: 0 for k in KEYS})
    c1 = dict(requests=5, **{k: 2.5 * 2 ** 20 for k in KEYS})
    assert _reader(name)(_ctx(outs, c0, c1)) == pytest.approx(value)


@pytest.mark.parametrize("name", ["loop.queue_wait_ms",
                                  "loop.prefill_wait_ms", "kv.moved_mib"])
def test_readers_read_nothing_from_a_program_without_stamps(name):
    """The parent program: outputs without the timeline, counters without
    the byte counts; and a window that finished no prefill."""
    bare = {1: SimpleNamespace(token_t=[11.0])}
    c = dict(requests=3)
    assert _reader(name)(_ctx(bare, c, dict(requests=5))) is None
    empty = {1: _out()}
    c0 = dict(requests=3, **{k: 0 for k in KEYS})
    assert _reader(name)(_ctx(empty, c0, dict(c0))) is None
