"""Operations and bytes the served work needs, from shapes alone.

Each architecture (``bench/arch/<arch>.py``) counts its own
``prefill_chunk``, ``decode_step`` and ``weight_bytes``. What they count
is the least a correct implementation must do: the matrix products of
every layer, attention over the positions a query may see, the output
head where a logit is needed, and one read of each weight and of each
live cache entry per program. What the program does beyond that
(attention over masked or padded positions, logits nobody reads, copies
of a whole page slab) is not counted: it is the waste the rooflines show.
All tensors are bfloat16 (``BYTES``).
"""
from __future__ import annotations

BYTES = 2


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
