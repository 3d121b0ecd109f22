"""KV bytes moved between tiers per prefill finished in the window, in MiB:
the ``PrefillWorker`` counters ``h2d_bytes`` (host-pool hits uploaded),
``d2h_bytes`` (the dense cache read back), ``put_bytes`` (stored into the
host pool) and ``stage_bytes`` (written into device pages), summed over
the window. A program without those counters reads nothing. Moves
``ttft_p90_ms``: each byte is moved before the request's first token."""

KEYS = ("h2d_bytes", "d2h_bytes", "put_bytes", "stage_bytes")


def read(ctx):
    a, b = ctx.counters0["prefill"], ctx.counters1["prefill"]
    n = b["requests"] - a["requests"]
    if not n or any(k not in b for k in KEYS):
        return None
    return sum(b[k] - a[k] for k in KEYS) / n / 2 ** 20
