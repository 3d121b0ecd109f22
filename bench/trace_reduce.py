"""From a profiler trace (``.xplane.pb``) to device busy time, device time
per program, and what the host was doing.

A TPU trace has, per chip, a plane ``/device:TPU:<n>`` whose line ``XLA
Ops`` holds every operation run and whose line ``XLA Modules`` holds
every program run (event stat ``run_id``). The host plane ``/host:CPU``
holds the benchmark's spans (``bench.<name>``, written as
``TraceAnnotation``) and, on the thread that enqueued it, a
``DoEnqueueProgram`` event with the same ``run_id`` for each program run.

* busy: the union of the ``XLA Ops`` intervals of a chip, averaged over
  the chips.
* a program run is attributed to the innermost ``bench.`` span that
  encloses its enqueue on the host clock: the host clock and the device
  clock differ by about a millisecond, and a span of the engine ends
  before the device work it enqueued when that work is asynchronous, so
  the enqueue is the dependable anchor.
* idle gaps between busy intervals are moved onto the host clock by the
  least (device start − enqueue) over matched runs, and attributed to
  the span that encloses their midpoint (``host.other`` where none does).
* a ``bench.mark`` annotation carries the arguments of the span that
  encloses it (``bench/spans.py``): what that call worked on. A span cut
  by the trace's end has none, and the rooflines leave it out.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field

from bench.spans import decode_info

SPAN_PREFIX = "bench."


@dataclass
class HostSpan:
    name: str
    start_ns: float      # host clock
    end_ns: float
    info: dict = None    # from its ``bench.mark``, None if it has none


@dataclass
class Run:
    """One program run on a device."""
    module: str          # e.g. ``jit__lambda(123...)``
    start_ns: float      # device clock
    dur_ns: float
    span: str            # enclosing host span, or ``host.other``
    span_start_ns: float = 0.0   # start of that span (host clock)
    span_index: int = -1         # into ``Reduced.spans``


@dataclass
class Reduced:
    window_s: float
    busy_s: float                 # averaged over chips
    n_chips: int
    runs: list = field(default_factory=list)
    spans: list = field(default_factory=list)          # HostSpan
    op_seconds: dict = field(default_factory=dict)     # op name -> s
    idle_by_span: dict = field(default_factory=dict)   # host span -> s

    def device_seconds(self, span: str, module_prefix: str = None,
                       marked: bool = False) -> float:
        """Device seconds of the runs enqueued in spans named ``span``
        (with ``marked``, only spans whose mark is in the trace)."""
        return sum(r.dur_ns for r in self.runs if r.span == span and (
            module_prefix is None or r.module.startswith(module_prefix))
            and (not marked or self.spans[r.span_index].info is not None)) \
            / 1e9

    def marked(self, span: str) -> list:
        """The spans named ``span`` that carry their mark."""
        return [h for h in self.spans if h.name == span
                and h.info is not None]


def _union_ns(intervals: list) -> tuple[float, list]:
    """Total length of a union of (start, end) intervals, and the gaps
    between its pieces."""
    total, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


class _Spans:
    """Host spans, innermost-first lookup by time."""

    def __init__(self, spans: list) -> None:
        self.spans = sorted(spans, key=lambda h: h.start_ns)
        self.starts = [h.start_ns for h in self.spans]

    def at(self, t: float):
        """Index of the innermost span holding ``t``, or None."""
        i = bisect.bisect_right(self.starts, t)
        best = None
        # spans nest a few deep at most (kv.* inside prefill.chunk); look
        # back a bounded way
        for j in range(i - 1, max(i - 64, -1), -1):
            h = self.spans[j]
            if h.start_ns <= t < h.end_ns and (
                    best is None or h.end_ns - h.start_ns <
                    self.spans[best].end_ns - self.spans[best].start_ns):
                best = j
        return best


def _stats(e) -> dict:
    return dict(e.stats)


def reduce_trace(path: str, span_prefix: str = SPAN_PREFIX) -> Reduced:
    """Reduce one ``.xplane.pb`` file."""
    from jax import profiler
    data = profiler.ProfileData.from_file(path)
    host_spans, marks, enqueues = [], [], {}
    devices = []
    t_lo = t_hi = None
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == span_prefix + "mark":
                        marks.append((e.start_ns, _stats(e)))
                    elif e.name.startswith(span_prefix):
                        host_spans.append(HostSpan(
                            e.name[len(span_prefix):], e.start_ns,
                            e.start_ns + e.duration_ns))
                    elif e.name == "DoEnqueueProgram":
                        rid = _stats(e).get("run_id")
                        if rid is not None:
                            enqueues[(_stats(e).get("device_ordinal", 0),
                                      rid)] = e.start_ns
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
        elif plane.name == "Task Environment":
            st = {k: v for k, v in plane.stats}
            if "profile_start_time" in st:
                t_lo = float(st["profile_start_time"])
                t_hi = float(st["profile_stop_time"])
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    spans = _Spans(host_spans)
    for t, st in marks:
        j = spans.at(t)
        if j is not None:
            spans.spans[j].info = decode_info(st)
    runs, busy, op_ns, gaps_all = [], 0.0, defaultdict(float), []
    skews = []
    for plane in devices:
        ordinal = int(plane.name[len("/device:TPU:"):])
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
                    name = e.name.split(" = ")[0].lstrip("%")
                    op_ns[name] += e.duration_ns
            elif line.name == "XLA Modules":
                modules = list(line.events)
        b, gaps = _union_ns(ops)
        busy += b
        for m in modules:
            rid = _stats(m).get("run_id")
            enq = enqueues.get((ordinal, rid))
            j = spans.at(enq) if enq is not None else None
            if enq is not None:
                skews.append(m.start_ns - enq)
            h = spans.spans[j] if j is not None else None
            runs.append(Run(m.name, m.start_ns, m.duration_ns,
                            h.name if h else "host.other",
                            h.start_ns if h else 0.0,
                            j if j is not None else -1))
        gaps_all += gaps
    skew = min(skews) if skews else 0.0
    idle = defaultdict(float)
    for s, e in gaps_all:
        j = spans.at((s + e) / 2 - skew)
        idle[spans.spans[j].name if j is not None else "host.other"] += \
            (e - s) / 1e9
    n = len(devices)
    if t_lo is not None and t_hi is not None and t_hi > t_lo:
        window_s = (t_hi - t_lo) / 1e9
    else:
        window_s = (max(h.end_ns for h in host_spans) -
                    min(h.start_ns for h in host_spans)) / 1e9
    return Reduced(window_s=window_s, busy_s=busy / n / 1e9, n_chips=n,
                   runs=runs, spans=spans.spans, op_seconds={k: v / 1e9 for k, v in op_ns.items()},
                   idle_by_span=dict(idle))


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device operations
    that took most time, and idle time by what the host was doing."""
    ops = sorted(r.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
