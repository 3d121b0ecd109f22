"""The program's own spans in a profiler trace, against the device's idle
time.

The serving engine opens ``serve.<name>`` spans (``repro.serving.tracing``)
while a profiler session runs: ``loop.*`` in the serving loop,
``prefill.*`` in a prefill, ``decode.*`` in a decode step. This module
reads them from one ``.xplane.pb`` file, nests each inside the span that
encloses it on its thread, and gives every idle gap of the device to the
innermost program span holding the gap's midpoint once the gap is moved
onto the host clock (by the least device start - enqueue over the matched
program runs, as ``bench/trace_reduce.py`` does). Idle time outside every
program span is given to ``none``.

It reads what ``reduce_trace`` does not keep; the harness does not call it
yet. To measure a cell with it, run the cell through this file::

    python3 bench/program_trace.py --workload qwen2_5_3b.sessions \\
        --seed 7 --seconds 51

which runs ``bench/run.py`` with ``--trace 1``, reduces the same trace
with ``reduce_program`` too, prints the run's result line and then one
line of program-span readings (``program_metrics``), and writes both to
``bench_out/program_trace/<workload>.<seed>.json``.
"""
from __future__ import annotations

import json
import sys
import time
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path

PREFIX = "serve."
NONE = "none"


@dataclass
class ProgramSpan:
    name: str            # without ``serve.``
    start_ns: float      # host clock
    end_ns: float
    thread: str          # the trace line (host thread) it ran on
    info: dict = field(default_factory=dict)
    parent: int = -1     # index of the enclosing span on its thread

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class ProgramTrace:
    window_s: float
    busy_s: float                # union of ``XLA Ops``, averaged over chips
    spans: list = field(default_factory=list)          # ProgramSpan
    gaps: list = field(default_factory=list)   # idle (start, end), host ns
    edge_s: float = 0.0          # idle before the first op, after the last
    skew_ns: float = 0.0         # device clock - host clock
    gap_span: list = field(default_factory=list)   # innermost span per gap
    host_events: int = 0         # events on the host plane

    def idle_by_span(self) -> dict:
        """Idle seconds by the name of the innermost program span."""
        out = defaultdict(float)
        for (s, e), j in zip(self.gaps, self.gap_span):
            out[self.spans[j].name if j is not None else NONE] += \
                (e - s) / 1e9
        return dict(out)

    def named(self, name: str) -> list:
        return [h for h in self.spans if h.name == name]

    def idle_inside(self, name: str) -> dict:
        """Idle seconds inside each span called ``name`` (its own or a
        descendant's), by index into ``spans``."""
        out = {i: 0.0 for i, h in enumerate(self.spans) if h.name == name}
        for (s, e), j in zip(self.gaps, self.gap_span):
            while j is not None and j >= 0:
                if j in out:
                    out[j] += (e - s) / 1e9
                    break
                j = self.spans[j].parent
        return out


def innermost(spans: list, times: list) -> list:
    """For each of ``times`` (ascending), the index into ``spans`` of the
    innermost span holding it, or None. Spans on one thread nest; across
    threads the shortest wins."""
    by_thread = defaultdict(list)
    for i, h in enumerate(spans):
        by_thread[h.thread].append(i)
    best = [None] * len(times)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
        stack, k = [], 0
        for q, t in enumerate(times):
            while k < len(idx) and spans[idx[k]].start_ns <= t:
                i = idx[k]
                k += 1
                while stack and spans[stack[-1]].end_ns <= spans[i].start_ns:
                    stack.pop()
                stack.append(i)
            while stack and spans[stack[-1]].end_ns <= t:
                stack.pop()
            if stack:
                j = stack[-1]
                if best[q] is None or spans[j].dur_ns < spans[best[q]].dur_ns:
                    best[q] = j
    return best


def nest(spans: list) -> None:
    """Set each span's ``parent``: the span enclosing it on its thread."""
    by_thread = defaultdict(list)
    for i, h in enumerate(spans):
        by_thread[h.thread].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
        stack = []
        for i in idx:
            h = spans[i]
            while stack and spans[stack[-1]].end_ns <= h.start_ns:
                stack.pop()
            h.parent = stack[-1] if stack else -1
            stack.append(i)


def attribute(pt: ProgramTrace) -> None:
    """Give each idle gap to the innermost span holding its midpoint."""
    order = sorted(range(len(pt.gaps)), key=lambda g: sum(pt.gaps[g]))
    mids = [sum(pt.gaps[g]) / 2 for g in order]
    found = innermost(pt.spans, mids)
    pt.gap_span = [None] * len(pt.gaps)
    for g, j in zip(order, found):
        pt.gap_span[g] = j


def _info(stats) -> dict:
    return {k: v for k, v in stats}


def reduce_program(path: str) -> ProgramTrace:
    """Reduce one ``.xplane.pb`` file to program spans and idle gaps."""
    from jax import profiler

    from bench.trace_reduce import _union_ns
    data = profiler.ProfileData.from_file(path)
    spans, enqueues, devices = [], {}, []
    host_events = 0
    window_s = None
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host_events += 1
                    if e.name.startswith(PREFIX):
                        spans.append(ProgramSpan(
                            e.name[len(PREFIX):], e.start_ns,
                            e.start_ns + e.duration_ns, line.name,
                            _info(e.stats)))
                    elif e.name == "DoEnqueueProgram":
                        st = _info(e.stats)
                        if st.get("run_id") is not None:
                            enqueues[(st.get("device_ordinal", 0),
                                      st["run_id"])] = e.start_ns
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
        elif plane.name == "Task Environment":
            st = _info(plane.stats)
            if "profile_start_time" in st:
                window_s = (float(st["profile_stop_time"]) -
                            float(st["profile_start_time"])) / 1e9
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    busy, gaps, edge, skews = 0.0, [], 0.0, []
    for plane in devices:
        ordinal = int(plane.name[len("/device:TPU:"):])
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            elif line.name == "XLA Modules":
                for m in line.events:
                    enq = enqueues.get((ordinal, _info(m.stats).get("run_id")))
                    if enq is not None:
                        skews.append(m.start_ns - enq)
        b, g = _union_ns(ops)
        busy += b
        gaps += g
        if ops and window_s is not None:
            edge += min(s for s, _ in ops) + \
                (window_s * 1e9 - max(e for _, e in ops))
    n = len(devices)
    skew = min(skews) if skews else 0.0
    if window_s is None:
        window_s = (busy + sum(e - s for s, e in gaps)) / n / 1e9
    pt = ProgramTrace(window_s=window_s, busy_s=busy / n / 1e9, spans=spans,
                      gaps=[(s - skew, e - skew) for s, e in gaps],
                      edge_s=edge / n / 1e9, skew_ns=skew,
                      host_events=host_events)
    nest(pt.spans)
    attribute(pt)
    return pt


# ---- readings ----------------------------------------------------------------

def readback_ms(pt: ProgramTrace):
    """Mean ``prefill.readback`` span, ms: the dense cache of a finished
    prefill read back to the host."""
    spans = pt.named("prefill.readback")
    if not spans:
        return None
    return sum(h.dur_ns for h in spans) / len(spans) / 1e6


def decode_host_ms(pt: ProgramTrace):
    """Mean device-idle time inside each ``decode.step`` span, ms: the
    host's share of a decode step."""
    idle = pt.idle_inside("decode.step")
    if not idle:
        return None
    return 1e3 * sum(idle.values()) / len(idle)


def idle_loop_share(pt: ProgramTrace):
    """Device-idle time whose innermost program span is a ``loop.*``
    span, as a percentage of the traced window."""
    if not pt.spans or pt.window_s <= 0:
        return None
    idle = sum(v for k, v in pt.idle_by_span().items()
               if k.startswith("loop."))
    return 100.0 * idle / pt.window_s


def program_metrics(pt: ProgramTrace) -> dict:
    """Everything a cell's program spans say, as one JSON-ready dict."""
    idle = pt.idle_by_span()
    total = sum(idle.values())
    counts = Counter(h.name for h in pt.spans)
    # a span open when the profiler starts or stops is not recorded: idle
    # outside every span before the first loop iteration in the trace or
    # after the last one lies in such cut spans
    its = pt.named("loop.iteration")
    lo = min((h.start_ns for h in its), default=0.0)
    hi = max((h.end_ns for h in its), default=0.0)
    cut = sum(e - s for (s, e), j in zip(pt.gaps, pt.gap_span)
              if j is None and not lo <= (s + e) / 2 <= hi) / 1e9
    # iterations with work run back to back; a pause of more than 5 ms
    # between two is the loop spinning with nothing to do
    its.sort(key=lambda h: h.start_ns)
    pauses = [(a.end_ns, b.start_ns) for a, b in zip(its, its[1:])
              if b.start_ns - a.end_ns > 5e6]
    empty = sum(e - s for (s, e), j in zip(pt.gaps, pt.gap_span)
                if j is None and any(a <= (s + e) / 2 <= b
                                     for a, b in pauses)) / 1e9
    return {
        "kv.readback_ms": readback_ms(pt),
        "decode.host_ms": decode_host_ms(pt),
        "device.idle_loop_share": idle_loop_share(pt),
        "window_s": pt.window_s, "busy_s": pt.busy_s,
        "idle_gaps_s": total, "edge_idle_s": pt.edge_s,
        "idle_in_spans_share": 100.0 * (1 - idle.get(NONE, 0.0) / total)
        if total else None,
        "idle_none_cut_s": cut,
        "idle_none_engine_empty_s": empty,
        "engine_empty_s": sum(b - a for a, b in pauses) / 1e9,
        "idle_in_spans_share_uncut": 100.0 * (
            1 - (idle.get(NONE, 0.0) - cut) / (total - cut))
        if total > cut else None,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "spans": dict(sorted(counts.items())),
        "spans_per_s": len(pt.spans) / pt.window_s if pt.window_s else None,
        "host_events": pt.host_events,
    }


def main(argv=None) -> int:
    """``bench/run.py`` with ``--trace 1``, the trace also reduced here."""
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root)]
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" not in argv:
        argv += ["--trace", "1"]
    import bench.run as run
    run._setup_env()
    import bench.trace_reduce as tr
    found = {}
    reduce_trace = tr.reduce_trace

    def both(path, *a, **kw):
        found["program"] = program_metrics(reduce_program(path))
        return reduce_trace(path, *a, **kw)

    tr.reduce_trace = both
    t0 = time.monotonic()
    rc = run.main(argv)
    found["run_s"] = time.monotonic() - t0
    print(json.dumps(found), flush=True)
    out = root / "bench_out" / "program_trace"
    out.mkdir(parents=True, exist_ok=True)
    wl = argv[argv.index("--workload") + 1]
    seed = argv[argv.index("--seed") + 1]
    (out / f"{wl}.{seed}.json").write_text(json.dumps(found, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main())
