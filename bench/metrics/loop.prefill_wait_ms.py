"""Mean time the window's requests spent between their first prefill chunk
and their first token other than in their own chunks, in ms: behind
decode steps, other prompts' chunks and the join (``RequestOutput``
``token_t[0] - t_first_chunk - prefill_busy_s``). A program without those
stamps reads nothing. Moves ``ttft_p90_ms``."""


def read(ctx):
    outs = ctx.window.loop.outputs
    waits = []
    for r in ctx.records:
        o = outs.get(r["req_id"])
        t1 = getattr(o, "t_first_chunk", None)
        if t1 is not None and o.token_t:
            waits.append(o.token_t[0] - t1 - o.prefill_busy_s)
    return 1e3 * sum(waits) / len(waits) if waits else None
