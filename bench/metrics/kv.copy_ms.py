"""Host time spent moving KV per prefilled request, in ms: host-pool reads
(``kv.get``) and stores (``kv.put``) and page-run writes into the device
pool (``kv.stage``), summed over the window, per prefill finished there.
The device copies that staging enqueues run after the span; the trace
attributes them. Moves ``ttft_p90_ms``."""


def read(ctx):
    w = ctx.window
    spans = [s for s in ctx.recorder.window(w.t_open, w.t_close)
             if s.name in ("kv.get", "kv.put", "kv.stage")]
    n = ctx.counters1["prefill"]["requests"] - ctx.counters0["prefill"]["requests"]
    if not n:
        return None
    return 1e3 * sum(s.dt for s in spans) / n
