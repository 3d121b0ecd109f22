"""Decode steps' share of their roofline, in percent: over the traced
window, the least time of each step (weights once, the live KV of every
active slot, by ``bench/flops.py``, with the cached lengths each step's
``bench.mark`` records; not the copies of the whole page slab that the
step makes, which are the waste this exposes) summed, over the device
time of the step programs (``jit__lambda`` runs enqueued inside those
``decode.step`` spans). Moves ``output_tok_s``."""


def read(ctx):
    red = ctx.reduced
    spans = [h for h in red.marked("decode.step") if h.info.get("seq_lens")]
    dev = red.device_seconds("decode.step", "jit__lambda", marked=True)
    if not spans or dev <= 0:
        return None
    least = sum(ctx.flops.least_seconds(*ctx.flops.decode_step(
        ctx.shape, h.info["seq_lens"]), ctx.peak) for h in spans)
    return 100.0 * least / dev
