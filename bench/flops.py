"""Operations and bytes the served work needs, from shapes alone.

These are the least a correct implementation must do: the matrix
products of every layer, attention over the positions a query may see,
the output head where a logit is needed, and one read of each weight and
of each live cache entry per program. What the program does beyond that
(attention over masked or padded positions, logits nobody reads, copies
of a whole page slab) is not counted: it is the waste the rooflines show.
All tensors are bfloat16 (2 bytes).
"""
from __future__ import annotations

from bench.model import Shape

BYTES = 2


def layer_weight_elems(s: Shape) -> int:
    """Matrix elements of one layer (q, k, v, o projections and SwiGLU)."""
    D, H, KV, Dh, F = s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff
    return D * (H + 2 * KV) * Dh + H * Dh * D + 3 * D * F


def weight_bytes(s: Shape) -> int:
    """Bytes of every weight a forward pass reads once: the layers and the
    output head (the input embedding is gathered row by row, a few rows a
    step, and counted with the activations, not here)."""
    return BYTES * (s.n_layers * layer_weight_elems(s)
                    + s.d_model * s.vocab_size)


def _attn_flops(s: Shape, visible: int) -> int:
    """QK^T and PV of one query over ``visible`` positions, every layer."""
    return 2 * 2 * s.n_layers * s.n_heads * s.head_dim * visible


def _kv_bytes(s: Shape, tokens: int) -> int:
    """bf16 K and V of ``tokens`` positions over every layer."""
    return tokens * s.kv_bytes_per_token


def prefill_chunk(s: Shape, lo: int, hi: int, last: bool) -> tuple[int, int]:
    """(flops, bytes) of one prefill chunk that extends a cache holding
    ``lo`` positions by positions ``lo .. hi-1`` (causal). ``last``: the
    chunk whose final position gives the first token, the one logit row a
    prefill needs."""
    T = hi - lo
    flops = 2 * T * s.n_layers * layer_weight_elems(s)
    # causal: the query at position p sees p + 1 positions
    flops += _attn_flops(s, T * lo + T * (T + 1) // 2)
    nbytes = BYTES * s.n_layers * layer_weight_elems(s)
    nbytes += _kv_bytes(s, lo) + _kv_bytes(s, T)     # read past, write new
    nbytes += 2 * BYTES * T * s.d_model              # embedded rows in, out
    if last:
        flops += 2 * s.d_model * s.vocab_size
        nbytes += BYTES * s.d_model * s.vocab_size
    return flops, nbytes


def decode_step(s: Shape, seq_lens: list[int]) -> tuple[int, int]:
    """(flops, bytes) of one decode step over the active slots, whose
    caches hold ``seq_lens`` positions before the step: one new token
    each, which attends its past and itself, and one logit row each."""
    B = len(seq_lens)
    per_token = 2 * (s.n_layers * layer_weight_elems(s)
                     + s.d_model * s.vocab_size)
    flops = B * per_token + sum(_attn_flops(s, n + 1) for n in seq_lens)
    nbytes = weight_bytes(s)
    nbytes += sum(_kv_bytes(s, n) for n in seq_lens) + _kv_bytes(s, B)
    nbytes += 2 * BYTES * B * s.d_model
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
