"""Share of the traced window in which no operation ran on the device, in
percent (1 - union of ``XLA Ops`` intervals over the window, averaged over
the chips). Moves ``output_tok_s``."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * (1.0 - r.busy_s / r.window_s) if r.window_s > 0 else None
