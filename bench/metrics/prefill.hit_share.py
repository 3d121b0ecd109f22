"""Share of prompt tokens served from the host KV pool instead of being
computed, over the prefills that finished in the window, in percent
(``PrefillWorker`` counters ``reused_blocks`` and ``computed_tokens``).
Moves ``ttft_p90_ms``: a hit skips its chunks."""

BLOCK = 512


def read(ctx):
    a, b = ctx.counters0["prefill"], ctx.counters1["prefill"]
    reused = (b["reused_blocks"] - a["reused_blocks"]) * BLOCK
    computed = b["computed_tokens"] - a["computed_tokens"]
    if reused + computed == 0:
        return None
    return 100.0 * reused / (reused + computed)
