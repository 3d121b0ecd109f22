"""Mean time the window's requests waited between ``ServingLoop.submit``
and their first prefill chunk, in ms (``RequestOutput.t_submit`` and
``t_first_chunk``, stamped by the program on the clock of ``token_t``).
A program without those stamps reads nothing. Moves ``ttft_p90_ms``."""


def read(ctx):
    outs = ctx.window.loop.outputs
    waits = []
    for r in ctx.records:
        o = outs.get(r["req_id"])
        t0 = getattr(o, "t_submit", None)
        t1 = getattr(o, "t_first_chunk", None)
        if t0 is not None and t1 is not None:
            waits.append(t1 - t0)
    return 1e3 * sum(waits) / len(waits) if waits else None
