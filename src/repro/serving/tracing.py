"""Spans of the serving engine, on the profiler's clock.

``span(name, **info)`` is a context manager around
``jax.profiler.TraceAnnotation(f"serve.{name}", **info)``. The spans exist
only while a profiler session runs (``jax.profiler.trace`` or
``start_trace``; TensorBoard and Perfetto show them on the host threads,
beside the ``DoEnqueueProgram`` events of the device programs they
launched). Outside a session a span costs one check of whether the
profiler is on, and records nothing.

A span's parent is the span enclosing it on the same thread. Spans of one
request carry ``req=<req_id>``. The names, by layer:

  loop.iteration     ``ServingLoop`` iteration that has work
    loop.admit       arrivals drained: routing (prompt hashes, pool
                     residency) and prefill start
    loop.join        finished prefills and restores into the decode batch
  prefill.chunk      ``ChunkedPrefill.advance``: one device chunk; ``req``
    prefill.lookup   prefix hashes, ``match_prefix`` or ``plan_fetch``
    prefill.load     cache set-up and host-pool hit blocks uploaded;
                     ``blocks``
    prefill.compute  the chunk's jitted extend, to its logits; ``lo``, ``hi``
    prefill.readback last chunk: first token and the dense cache to host
    prefill.store    last chunk: fresh blocks into the host pool
    prefill.stage    last chunk: the KV written into device pages
  decode.step        ``DecodeWorker.step``; ``slots``
    decode.prepare   a writable page for every active slot
    decode.launch    block tables uploaded and the jitted step dispatched
    decode.readout   each slot's token read back, finished slots freed

No span stays open across a ``yield`` of the prefill generators: every
child opens and closes inside one ``advance()``.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

PREFIX = "serve."

_OFF = contextlib.nullcontext()
_on = TraceAnnotation.is_enabled


def span(name: str, **info):
    """A ``serve.<name>`` profiler span carrying ``info`` as arguments."""
    if not _on():
        return _OFF
    return TraceAnnotation(PREFIX + name, **info)
