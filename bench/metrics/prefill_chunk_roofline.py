"""Prefill chunks' share of their roofline, in percent: over the traced
window, the least time of each chunk (the larger of its operations over
the chip's bf16 peak and its bytes over HBM bandwidth, from shapes by
``bench/flops.py``, with the shapes each chunk's ``bench.mark`` records)
summed, over the device time of the chunk programs (``jit__lambda`` runs
enqueued inside those ``prefill.chunk`` spans). Moves ``ttft_p90_ms``."""


def read(ctx):
    red = ctx.reduced
    spans = red.marked("prefill.chunk")
    dev = red.device_seconds("prefill.chunk", "jit__lambda", marked=True)
    if not spans or dev <= 0:
        return None
    least = sum(ctx.flops.least_seconds(*ctx.flops.prefill_chunk(
        ctx.shape, h.info["lo"], h.info["hi"], bool(h.info["last"])),
        ctx.peak) for h in spans)
    return 100.0 * least / dev
