"""Share of the prefills finished in the window that staged their KV into
device pages straight from the device, in percent (``PrefillWorker``
counters ``device_stages`` and ``host_stages``). A program without those
counters reads nothing. Moves ``tbt_p99_ms``: a prefill that cannot stage
on the device reads its whole cache back to the host while every decoding
stream waits."""


def read(ctx):
    a, b = ctx.counters0["prefill"], ctx.counters1["prefill"]
    if "device_stages" not in b or "host_stages" not in b:
        return None
    dev = b["device_stages"] - a["device_stages"]
    host = b["host_stages"] - a["host_stages"]
    if dev + host == 0:
        return None
    return 100.0 * dev / (dev + host)
