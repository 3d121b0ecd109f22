"""Share of the window's wall time the engine spent in prefill chunks
(``prefill.chunk`` spans), in percent. Moves ``tbt_p99_ms``: a chunk runs
between two decode steps, so its time is a gap in every decoding stream."""


def read(ctx):
    w = ctx.window
    chunks = ctx.recorder.window(w.t_open, w.t_close, "prefill.chunk")
    if not chunks:
        return None
    return 100.0 * sum(s.dt for s in chunks) / (w.t_close - w.t_open)
