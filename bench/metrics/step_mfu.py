"""Model FLOP utilisation of the whole serving step, in percent: the
operations the traced window's prefill chunks and decode steps needed
(``bench/flops.py``, with the shapes their ``bench.mark``s record), over
the traced window's length times the chip's bf16 peak. It bounds every
kernel's gain: a program taken off the path leaves its roofline silent,
not this. Moves ``output_tok_s``."""


def read(ctx):
    red = ctx.reduced
    f = sum(ctx.flops.prefill_chunk(ctx.shape, h.info["lo"], h.info["hi"],
                                    bool(h.info["last"]))[0]
            for h in red.marked("prefill.chunk"))
    f += sum(ctx.flops.decode_step(ctx.shape, h.info["seq_lens"])[0]
             for h in red.marked("decode.step") if h.info.get("seq_lens"))
    if not f:
        return None
    return 100.0 * f / (red.window_s * ctx.peak["bf16_flops_per_s"])
