"""Median time to first token of the run's requests (host clock, from when
each was due, or sent in a closed loop): the steadier companion of the
``ttft_p90_ms`` tail."""
import numpy as np


def read(ctx):
    t = [r["ttft"] for r in ctx.records if r["ttft"] is not None]
    return float(np.median(t)) * 1e3 if t else None
