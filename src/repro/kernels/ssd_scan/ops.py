"""Public op: chunked SSD scan (kernel or oracle dispatch)."""
from __future__ import annotations

from repro.kernels.ssd_scan.kernel import ssd_scan as _kernel
from repro.kernels.ssd_scan.ref import ssd_scan_ref as _ref


def ssd_scan_op(x, dt, A, B, C, h0=None, *, chunk: int = 256,
                use_pallas: bool = False, interpret: bool = False):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n).
    ``interpret`` (the Pallas interpreter) is only ever the caller's."""
    if not use_pallas:
        return _ref(x, dt, A, B, C, chunk=chunk, h0=h0)
    return _kernel(x, dt, A, B, C, h0, chunk=chunk, interpret=interpret)
