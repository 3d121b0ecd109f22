"""The plain reference: the published architecture's forward pass in float32.

Straight ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no cache, no
batching across requests, one sequence at a time, the layers one after
another with each layer's weights made again from the seed
(``bench.weights``). It imports nothing of the program and reads nothing
the program made.

The layers are the architecture's (``bench/arch/<arch>.py``: its
``layer_weights`` and ``layer``, each told the layer's index). This module
holds what every architecture shares: the embedding, the loop over the
layers, the final RMSNorm and the output head (the embedding transposed
where tied), the served tokens' logit gaps, and the helpers a layer uses
(``mm``, ``q8``, ``rms``, ``rope``).

``precision="fp8"`` is the control: the same pass with both operands of
every matrix product rounded to float8 e4m3 (scaled per row and per
column, products summed in float32), the step below the bfloat16 the
configurations serve in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
T_BUCKET = 512        # sequences are padded up to a multiple of this
HEAD_ROWS = 256       # positions per block of the output head


def padded_rows(vocab: int) -> int:
    """Rows of the embedding as the checkpoint lays it out (a multiple of
    256, zero past the vocabulary): the random stream depends on it."""
    return -(-vocab // 256) * 256


def q8(x, axis):
    """Round to e4m3 with one scale per slice along ``axis`` (kept dims)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(a, b, fp8: bool):
    """(..., K) @ (K, N) in float32; fp8 rounds a per row, b per column."""
    if fp8:
        a, b = q8(a, -1), q8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (T, heads, Dh); rotation of the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@partial(jax.jit, static_argnames=("shape", "rows"))
def _head(root, *, shape, rows: int):
    """(D, vocab) float32 output head, published vocabulary only."""
    if shape.tie_embeddings:
        w = weights.embedding(shape, root, rows).T
    else:
        w = weights.lm_head(shape, root, rows)
    return w[:, :shape.vocab_size].astype(jnp.float32)


@partial(jax.jit, static_argnames=("shape", "rows"))
def _embed(root, ids, *, shape, rows: int):
    return weights.embedding(shape, root, rows)[ids].astype(jnp.float32)


@partial(jax.jit, static_argnames=("fp8",))
def _head_rows(h, w, *, fp8: bool):
    """The logits of each row of ``h``."""
    return mm(h, w, fp8)


def hidden_states(arch, shape, seed: int, seqs: list, *,
                  precision: str = "f32") -> list:
    """Final normed hidden states (T, D) of each token sequence, through
    the layers of ``arch`` (``bench/arch/<arch>.py``) one at a time."""
    fp8 = precision == "fp8"
    root = weights.root_key(seed)
    rows = padded_rows(shape.vocab_size)
    xs = []
    for ids in seqs:
        T = len(ids)
        Tp = -(-T // T_BUCKET) * T_BUCKET
        pad = np.zeros(Tp, np.int32)
        pad[:T] = ids
        x = _embed(root, jnp.asarray(pad), shape=shape, rows=rows)
        xs.append(x.at[T:].set(0.0))
    for index in range(shape.n_layers):
        t = arch.layer_weights(shape, root, index)
        xs = [arch.layer(x, t, index, shape=shape, fp8=fp8) for x in xs]
    fin = weights.final_norm(shape, root).astype(jnp.float32)
    return [rms(x[:len(ids)], fin, shape.norm_eps)
            for x, ids in zip(xs, seqs)]


def served_gaps(arch, shape, seed: int, prompts: list, served: list, *,
                control: bool = False) -> dict:
    """For each request (prompt, served tokens): at every served position,
    how far the logit of a token lies under the reference's best, in
    float32. ``"served"``: the served token's gap. With ``control`` also
    ``"control"``: the gap of the token the fp8 pass puts first at that
    position (the served tokens are still the context), the control put
    in the program's place. One float64 array per request in each.
    """
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, served)]
    # the rows that predict each served token: the last prompt position,
    # then every served token but the last
    picks = [np.arange(len(p) - 1, len(p) - 1 + len(o))
             for p, o in zip(prompts, served)]
    root = weights.root_key(seed)
    rows = padded_rows(shape.vocab_size)
    w = _head(root, shape=shape, rows=rows)
    chosen = {"served": [np.asarray(o, np.int64) for o in served]}
    if control:
        hc = hidden_states(arch, shape, seed, seqs, precision="fp8")
        chosen["control"] = []
        for h, rows_i in zip(hc, picks):
            out = []
            for r0 in range(0, len(rows_i), HEAD_ROWS):
                z = _head_rows(h[rows_i[r0:r0 + HEAD_ROWS]], w, fp8=True)
                out.append(np.asarray(jnp.argmax(z, -1)))
            chosen["control"].append(np.concatenate(out))
        del hc
    hr = hidden_states(arch, shape, seed, seqs)
    gaps = {k: [] for k in chosen}
    for i, (h, rows_i) in enumerate(zip(hr, picks)):
        out = {k: [] for k in chosen}
        for r0 in range(0, len(rows_i), HEAD_ROWS):
            z = _head_rows(h[rows_i[r0:r0 + HEAD_ROWS]], w, fp8=False)
            best = jnp.max(z, -1)
            for k, toks in chosen.items():
                tk = jnp.asarray(toks[i][r0:r0 + HEAD_ROWS])
                inside = tk < shape.vocab_size
                zt = jnp.take_along_axis(
                    z, jnp.clip(tk, 0, shape.vocab_size - 1)[:, None],
                    1)[:, 0]
                out[k].append(np.asarray(jnp.where(inside, best - zt,
                                                   jnp.inf), np.float64))
        for k in chosen:
            gaps[k].append(np.concatenate(out[k]))
    return gaps
