"""Mean wall time of ``DecodeWorker.step`` in the window, in ms
(``decode.step`` spans; the step waits for its tokens, so the span holds
its device time). Moves ``tbt_p99_ms``."""


def read(ctx):
    w = ctx.window
    steps = ctx.recorder.window(w.t_open, w.t_close, "decode.step")
    if not steps:
        return None
    return 1e3 * sum(s.dt for s in steps) / len(steps)
