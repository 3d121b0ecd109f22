"""Public op: chunked-prefill flash attention (jit wrapper + dispatch).

``use_pallas`` selects the Pallas kernel (TPU target) vs the pure-jnp
oracle; ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
validation) and is only ever the caller's choice. The model forward
defaults to the oracle so the dry-run lowers cleanly on the CPU backend.
"""
from __future__ import annotations

from repro.kernels.flash_prefill.kernel import flash_prefill as _kernel
from repro.kernels.flash_prefill.ref import flash_prefill_ref as _ref


def flash_prefill_attention(q, k, v, *, q_offset: int = 0, window: int = 0,
                            use_pallas: bool = False,
                            interpret: bool = False):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D) → (B, Sq, H, D)."""
    if not use_pallas:
        return _ref(q, k, v, q_offset=q_offset, window=window)
    # largest MXU-friendly block that divides the sequence (tests sweep
    # tiny/ragged shapes; production shapes take the full 128)
    def block(s: int) -> int:
        return next(b for b in (128, 64, 32, 16, 8, 4, 2, 1) if s % b == 0)

    return _kernel(q, k, v, q_offset=q_offset, window=window,
                   bq=block(q.shape[1]), bk=block(k.shape[1]),
                   interpret=interpret)
