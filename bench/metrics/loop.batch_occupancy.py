"""Mean active decode slots per step in the window: decode tokens emitted
over decode steps (``decode.step`` spans, which record the active slots).
Moves ``output_tok_s``: more slots per step, more tokens per step."""


def read(ctx):
    steps = ctx.recorder.window(ctx.window.t_open, ctx.window.t_close,
                                "decode.step")
    if not steps:
        return None
    return sum(len(s.info["seq_lens"]) for s in steps) / len(steps)
