"""Spans and counters around the public calls into each layer.

The benchmark wraps methods of the engine objects it built (never the
program's classes or modules) so that each call into a layer records a
span: its name, host start and end, and what the call worked on. While a
profiler trace runs (``annotate``), each span is also a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, and what the
call worked on is written as a zero-length ``bench.mark`` annotation
nested at its end (its arguments are the span's ``info``), so the trace
alone says which device programs each span enqueued and how much work
they had to do.

Span names (the trace reduction and the metric readers use them):

  prefill.chunk   ``ChunkedPrefill.advance``: one device chunk of a prefill
                  (the first also looks the prefix up and reads hits; the
                  last also stores and stages the KV); info ``S``, ``lo``,
                  ``hi``, ``last``
  kv.get          ``HostKVPool.get``: host-pool hit blocks read
  kv.put          ``HostKVPool.put``: fresh blocks stored
  kv.stage        ``DevicePagePool.write_run``: a run of KV pages written
  decode.step     ``DecodeWorker.step``: one continuous-batching step; info
                  ``seq_lens``, the active slots' cached lengths before it
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import jax

BLOCK = 512
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclass
class Span:
    name: str
    t0: float           # time.monotonic() seconds
    t1: float
    info: dict = field(default_factory=dict)

    @property
    def dt(self) -> float:
        return self.t1 - self.t0


def encode_info(info: dict) -> dict:
    """A span's info as ``TraceAnnotation`` arguments: scalars, lists
    joined by ``_`` (the trace's own encoding splits on commas)."""
    return {k: "_".join(map(str, v)) if isinstance(v, (list, tuple)) else
            int(v) if isinstance(v, bool) else v for k, v in info.items()}


def decode_info(stats: dict) -> dict:
    """The inverse of ``encode_info`` for the keys spans use."""
    out = dict(stats)
    if "seq_lens" in out:
        s = str(out["seq_lens"])
        out["seq_lens"] = [int(x) for x in s.split("_")] if s else []
    for k in ("S", "lo", "hi", "last", "i"):
        if k in out:
            out[k] = int(out[k])
    return out


class Recorder:
    """In-memory spans of the engine thread, plus per-prefill facts."""

    def __init__(self) -> None:
        self.spans: list[Span] = []
        self.annotate = False
        self.recording = False
        self.prefills: dict = {}        # id(tokens) -> facts of its prefill
        self._lock = threading.Lock()

    def span(self, name: str, fn, *args, info=None, after=None, **kw):
        """Call ``fn``; ``after(result)`` may add to ``info`` once it ran."""
        info = {} if info is None else info
        ann = self.annotate
        t0 = time.monotonic()
        if ann:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                out = fn(*args, **kw)
                if after is not None:
                    after(out)
                with jax.profiler.TraceAnnotation("bench.mark",
                                                  **encode_info(info)):
                    pass
        else:
            out = fn(*args, **kw)
            if after is not None:
                after(out)
        t1 = time.monotonic()
        if self.recording:
            with self._lock:
                self.spans.append(Span(name, t0, t1, info))
        return out

    def window(self, t0: float, t1: float, name: str = None) -> list[Span]:
        return [s for s in self.spans if t0 <= s.t0 < t1
                and (name is None or s.name == name)]


class _TracedPrefill:
    """What ``PrefillWorker.start`` returned, with ``advance`` recorded.
    A proxy (not a method set on the program's object) so that nothing
    the program holds refers back to the benchmark's wrapper."""

    def __init__(self, cp, rec: Recorder, facts: dict, chunk: int,
                 last_hit: dict) -> None:
        self._cp = cp
        self._rec = rec
        self._facts = facts
        self._chunk = chunk
        self._last_hit = last_hit

    def __getattr__(self, name):
        return getattr(self._cp, name)

    def advance(self) -> bool:
        facts = self._facts
        first = facts["prefix"] is None
        if first:
            self._last_hit["blocks"] = 0
        info = {"S": facts["S"], "i": facts["chunks"]}

        def after(done):
            if first:
                n = min(self._last_hit["blocks"], (facts["S"] - 1) // BLOCK)
                facts["prefix"] = n * BLOCK
            lo = facts["prefix"] + info["i"] * self._chunk
            info.update(lo=lo, hi=min(lo + self._chunk, facts["S"]),
                        last=bool(done))
            facts["chunks"] += 1
            if done:
                facts["reused_tokens"] = self._cp.result.reused_blocks * BLOCK

        return self._rec.span("prefill.chunk", self._cp.advance, info=info,
                              after=after)

    def drain(self):
        while not self.advance():
            pass
        return self._cp.result


def instrument(rec: Recorder, pws, dw, pool) -> None:
    """Wrap the engine's layer entry points (instance attributes only)."""
    pp = dw.page_pool
    last_hit = {"blocks": 0}

    def match_prefix(hash_ids, _orig=pool.match_prefix):
        n = _orig(hash_ids)
        last_hit["blocks"] = n
        return n

    def get(hash_ids, _orig=pool.get):
        return rec.span("kv.get", _orig, hash_ids,
                        info={"blocks": len(hash_ids)})

    def put(hash_ids, k, v, start_pos=0, _orig=pool.put):
        return rec.span("kv.put", _orig, hash_ids, k, v, start_pos=start_pos,
                        info={"blocks": len(hash_ids)})

    pool.match_prefix, pool.get, pool.put = match_prefix, get, put
    if pp is not None:
        def write_run(pages, k, v, _orig=pp.write_run):
            return rec.span("kv.stage", _orig, pages, k, v,
                            info={"pages": len(pages)})
        pp.write_run = write_run

    for pw in pws:
        def start(tokens, session=None, _orig=pw.start, _chunk=pw.chunk):
            cp = _orig(tokens, session=session)
            facts = {"S": cp.prompt_len, "prefix": None, "chunks": 0}
            rec.prefills[id(tokens)] = facts
            return _TracedPrefill(cp, rec, facts, _chunk, last_hit)

        pw.start = start

    def step(_orig=dw.step):
        lens = [int(dw.seq_lens[i]) for i, s in enumerate(dw.slots)
                if s is not None]
        return rec.span("decode.step", _orig, info={"seq_lens": lens})

    dw.step = step


class CompileCounter:
    """Backend compilations and persistent-cache loads while ``counting``,
    from ``jax.monitoring`` events (registered once per process)."""

    def __init__(self) -> None:
        self.counting = False
        self.compiles = 0
        self.cache_loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if self.counting and event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if self.counting and event == CACHE_HIT_EVENT:
            self.cache_loads += 1
