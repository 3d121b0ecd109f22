"""The DeepSeek-V3 decoder layer (Moonlight, Kimi), as one chip's share of
an expert-parallel deployment.

Every layer: RMSNorm; multi-head latent attention (MLA): ``q_proj`` maps
the normed state straight to each head's 128 ``nope`` and 64 ``rope``
query dims (``q_lora_rank`` null), ``kv_a_proj_with_mqa`` to a 512-wide
latent ``c_kv`` (RMS-normed) and one 64-wide ``k_pe`` shared by every
head, ``kv_b_proj`` expands ``c_kv`` to each head's 128 key dims and 128
value dims; rotary embedding at base ``rope_theta`` on the rope dims,
softmax scale (nope + rope)^-0.5, ``o_proj``; residual. Then RMSNorm and
the FFN: a dense SwiGLU of width ``intermediate_size`` in the leading
``first_k_dense_replace`` layers, else the expert layer, residual.

The expert layer: router logits over all ``n_experts`` (the published
64) in float32, scores ``sigmoid``, the ``top_k`` picked on score + the
selection bias (``noaux_tc`` with one group), gates the picked scores
renormalised and × ``routed_scaling_factor``; routed SwiGLU experts of
width ``moe_intermediate_size``, plus a shared SwiGLU of ``n_shared``
times that width which every token takes. This chip holds experts
[``expert_first``, + ``n_held``) of each expert layer (the configuration
states the deployment) and computes only their part of the routed sum,
for the tokens routed to them; what the absent experts add is left out,
in the program and in this reference alike. The program serves it as a
``ModelConfig`` with ``MLAConfig`` and a held ``MoEConfig``.

Departures from the checkpoint, each a fixed relabelling of random
weights: rotary embedding by halves where the checkpoint interleaves
pairs (a fixed permutation of the rope dims of ``q_proj`` and
``kv_a_proj_with_mqa``); the held experts are the first ``n_held`` ids.

This file is the architecture's whole part of the benchmark
(``bench/model.py`` lists the contract).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import weights
from bench.flops import BYTES
from bench.reference import HI, mm, q8, rms, rope
from bench.weights import DTYPE, normal

Q_BLOCK = 512         # query rows per attention block of the reference


@dataclass(frozen=True)
class Shape:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    q_nope: int
    q_rope: int
    v_head: int
    kv_rank: int
    d_ff: int                 # the leading dense layers' SwiGLU
    first_dense: int
    n_experts: int            # the router's width (the published count)
    n_held: int               # experts held here, [expert_first, + n_held)
    expert_first: int
    top_k: int
    d_ff_expert: int
    n_shared: int
    routed_scale: float
    vocab_size: int
    context: int              # max_position_embeddings
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    expert_spread: float      # assumed: see ``expert_tensors``
    bias_std: float           # assumed: the selection bias's draw
    expert_scale: float       # assumed: see ``expert_tensors``
    qk_scale: float           # assumed: see ``attn_tensors``

    @property
    def qk_head(self) -> int:
        return self.q_nope + self.q_rope

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 ``c_kv`` and ``k_pe`` of one token over every layer."""
        return BYTES * self.n_layers * (self.kv_rank + self.q_rope)


def load(name: str, raw: dict) -> Shape:
    """The shape of a configuration file's published keys, its
    ``deployment`` (which experts this chip holds of how many) and its
    ``assumed`` sizes."""
    dep, assumed = raw["deployment"], raw["assumed"]
    first, end = dep["experts_held"]
    checks = {"hidden_act": "silu", "scoring_func": "sigmoid",
              "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
              "q_lora_rank": None, "norm_topk_prob": True,
              "moe_layer_freq": 1}
    for key, want in checks.items():
        if raw[key] != want:
            raise ValueError(f"{name}: {key}={raw[key]!r}, this "
                             f"architecture serves {want!r}")
    if end - first != raw["n_routed_experts"]:
        raise ValueError(f"{name}: holds experts [{first}, {end}) but "
                         f"n_routed_experts is {raw['n_routed_experts']}")
    return Shape(
        name=name, n_layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"], n_heads=raw["num_attention_heads"],
        q_nope=raw["qk_nope_head_dim"], q_rope=raw["qk_rope_head_dim"],
        v_head=raw["v_head_dim"], kv_rank=raw["kv_lora_rank"],
        d_ff=raw["intermediate_size"],
        first_dense=raw["first_k_dense_replace"],
        n_experts=dep["router_experts"], n_held=raw["n_routed_experts"],
        expert_first=first, top_k=raw["num_experts_per_tok"],
        d_ff_expert=raw["moe_intermediate_size"],
        n_shared=raw["n_shared_experts"],
        routed_scale=float(raw["routed_scaling_factor"]),
        vocab_size=raw["vocab_size"],
        context=raw["max_position_embeddings"],
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        expert_spread=float(assumed["expert_spread"]),
        bias_std=float(assumed["selection_bias_std"]),
        expert_scale=float(assumed["expert_scale"]),
        qk_scale=float(assumed["qk_scale"]))


def program_config(s: Shape):
    """The program's ``ModelConfig`` for these shapes (its latent
    attention runs ``n_heads`` heads, never padded)."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name=s.name, kind="moe", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_heads, head_dim=s.v_head,
        d_ff=s.d_ff, vocab_size=s.vocab_size, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tie_embeddings, remat=False,
        mla=MLAConfig(kv_lora_rank=s.kv_rank, qk_nope_head_dim=s.q_nope,
                      qk_rope_head_dim=s.q_rope, v_head_dim=s.v_head),
        moe=MoEConfig(n_experts=s.n_experts, top_k=s.top_k,
                      d_ff_expert=s.d_ff_expert, routed_scale=s.routed_scale,
                      d_ff_shared=s.n_shared * s.d_ff_expert,
                      first_dense=s.first_dense,
                      expert_first=s.expert_first, n_held=s.n_held))


# ---- weights ----------------------------------------------------------------

def _key(root, name, layer):
    return jax.random.fold_in(weights.name_key(root, name), layer)


def _gain(root, name, layer, n):
    g = 1.0 + 0.1 * jax.random.normal(_key(root, name, layer), (n,))
    return g.astype(DTYPE)


def attn_tensors(s: Shape, root: jax.Array, layer) -> dict:
    """One layer's MLA tensors, served type. q and the key parts (``k_pe``
    columns of ``kv_a``, ``k_nope`` columns of ``kv_b``) are drawn
    ``qk_scale`` wider, so that attention is peaked (``bench/weights.py``
    ``QK_SCALE`` says why). The configuration assumes the scale: at the
    dense cells' 1.5 the bf16 program's own error at these shapes moves
    served tokens within 2-3x of a reversed router, and no limit of the
    check tells the two apart (``PERF.md`` section 2)."""
    D, H = s.d_model, s.n_heads
    k = partial(_key, root, layer=layer)
    s_q = (2.0 / (D + H * s.qk_head)) ** 0.5
    s_a = (2.0 / (D + s.kv_rank + s.q_rope)) ** 0.5
    a_cols = jnp.where(jnp.arange(s.kv_rank + s.q_rope) < s.kv_rank,
                       1.0, s.qk_scale)
    b_cols = jnp.tile(jnp.where(jnp.arange(s.q_nope + s.v_head) < s.q_nope,
                                s.qk_scale, 1.0), H)
    wkv_a = s_a * a_cols * jax.random.normal(k("wkv_a"),
                                             (D, s.kv_rank + s.q_rope))
    wkv_b = s.kv_rank ** -0.5 * b_cols * jax.random.normal(
        k("wkv_b"), (s.kv_rank, H * (s.q_nope + s.v_head)))
    return dict(
        attn_ln=_gain(root, "attn_ln", layer, D),
        wq=normal(k("wq"), (D, H * s.qk_head), s_q * s.qk_scale),
        wkv_a=wkv_a.astype(DTYPE),
        kv_ln=_gain(root, "kv_ln", layer, s.kv_rank),
        wkv_b=wkv_b.astype(DTYPE),
        wo=normal(k("wo"), (H * s.v_head, D), s_q),
    )


def dense_tensors(s: Shape, root: jax.Array, layer) -> dict:
    """A leading layer: MLA and the dense SwiGLU."""
    D, F = s.d_model, s.d_ff
    sc = (2.0 / (D + F)) ** 0.5
    k = partial(_key, root, layer=layer)
    return dict(attn_tensors(s, root, layer),
                ffn_ln=_gain(root, "ffn_ln", layer, D),
                w1=normal(k("w1"), (D, F), sc), w2=normal(k("w2"), (F, D), sc),
                w3=normal(k("w3"), (D, F), sc))


def expert_tensors(s: Shape, root: jax.Array, layer) -> dict:
    """An expert layer: MLA, the router and its selection bias (float32,
    as the checkpoint keeps ``e_score_correction_bias``), the held
    experts and the shared expert.

    The program routes from bf16 hidden states and picks another expert
    than the float32 reference wherever two scores nearly tie (a few in a
    hundred token-layers). Where the tie is between an expert held here
    and one held elsewhere, a whole gated term is there in one pass and
    not in the other. Three assumed sizes keep such flips from moving the
    served tokens as far as a real fault does (``PERF.md`` section 2):

    - ``expert_scale``: the held experts' three matrices are drawn at this
      share of the scale the dense and shared SwiGLUs use, so that a
      routed term is a small part of a layer's output;
    - ``expert_spread``: each held expert is (common + spread x its own) /
      sqrt(1 + spread^2): near-independent experts, so that a reversed
      router, which hands tokens to other held experts, changes their
      outputs (a tie between two held experts is rare at 8 of 64);
    - ``bias_std``: the selection bias's spread, beside the scores'."""
    D, E, F = s.d_model, s.n_held, s.d_ff_expert
    Fs = s.n_shared * F
    sc = s.expert_scale * (2.0 / (D + F)) ** 0.5
    ss = (2.0 / (D + Fs)) ** 0.5
    k = partial(_key, root, layer=layer)

    def experts(name, shape):
        base = jax.random.normal(k(name), shape)
        own = jax.random.normal(k(name + "_own"), (E, *shape))
        w = (base + s.expert_spread * own) / (1 + s.expert_spread ** 2) ** 0.5
        return (sc * w).astype(DTYPE)

    return dict(
        attn_tensors(s, root, layer),
        ffn_ln=_gain(root, "ffn_ln", layer, D),
        router=normal(k("router"), (D, s.n_experts), D ** -0.5),
        bias=s.bias_std * jax.random.normal(k("bias"), (s.n_experts,)),
        w1=experts("w1", (D, F)), w2=experts("w2", (F, D)),
        w3=experts("w3", (D, F)),
        sw1=normal(k("sw1"), (D, Fs), ss), sw2=normal(k("sw2"), (Fs, D), ss),
        sw3=normal(k("sw3"), (D, Fs), ss),
    )


def _attn_tree(t: dict) -> dict:
    return dict(ln=t["attn_ln"], wq=t["wq"], wkv_a=t["wkv_a"],
                kv_ln=t["kv_ln"], wkv_b=t["wkv_b"], wo=t["wo"])


def program_params(s: Shape, seed: int, padded_vocab: int) -> dict:
    """The program's tree (``models/transformer.py`` ``init_params`` for
    latent attention): ``dense`` (``attn``, ``mlp``) for the leading
    layers, and ``attn`` and ``moe`` (``ln``, ``router``, ``bias``,
    ``w1``/``w2``/``w3`` of the held experts, ``shared``) stacked over the
    expert layers, made on the device in one jitted call."""

    def make(root):
        ids = jnp.arange(s.n_layers, dtype=jnp.uint32)
        d = jax.lax.map(lambda l: dense_tensors(s, root, l),
                        ids[:s.first_dense])
        e = jax.lax.map(lambda l: expert_tensors(s, root, l),
                        ids[s.first_dense:])
        return dict(
            weights.head_params(s, root, padded_vocab),
            dense=dict(attn=_attn_tree(d), mlp=dict(
                ln=d["ffn_ln"], w1=d["w1"], w2=d["w2"], w3=d["w3"])),
            attn=_attn_tree(e),
            moe=dict(ln=e["ffn_ln"], router=e["router"], bias=e["bias"],
                     w1=e["w1"], w2=e["w2"], w3=e["w3"],
                     shared=dict(w1=e["sw1"], w2=e["sw2"], w3=e["sw3"])))

    return jax.jit(make)(weights.root_key(seed))


# ---- the reference layer ----------------------------------------------------

def _swiglu(m, w1, w3, w2, fp8):
    return mm(jax.nn.silu(mm(m, w1, fp8)) * mm(m, w3, fp8), w2, fp8)


def _attend(q, k, v, fp8: bool):
    """Causal attention of (T, H, dq) queries over (T, H, dq) keys and
    (T, H, dv) values, ``Q_BLOCK`` query rows at a time (each against
    every key, the later ones masked), in one loop that compiles once."""
    T, H, dq = q.shape
    pos = jnp.arange(T)

    def block(i):
        q0 = i * Q_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / dq ** 0.5
        mask = (q0 + jnp.arange(Q_BLOCK))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        if fp8:
            p = q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    return out.reshape(T, H * v.shape[-1])


@partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, t, *, shape: Shape, fp8: bool):
    """One decoder layer over a whole (T, D) sequence, MLA expanded (T a
    multiple of ``Q_BLOCK``)."""
    s = shape
    T = x.shape[0]
    H = s.n_heads
    pos = jnp.arange(T)
    a = rms(x, t["attn_ln"], s.norm_eps)
    q = mm(a, t["wq"], fp8).reshape(T, H, s.qk_head)
    q = jnp.concatenate([q[..., :s.q_nope],
                         rope(q[..., s.q_nope:], pos, s.rope_theta)], -1)
    kv_a = mm(a, t["wkv_a"], fp8)
    c_kv = rms(kv_a[:, :s.kv_rank], t["kv_ln"], s.norm_eps)
    k_pe = rope(kv_a[:, None, s.kv_rank:], pos, s.rope_theta)
    kv = mm(c_kv, t["wkv_b"], fp8).reshape(T, H, s.q_nope + s.v_head)
    k = jnp.concatenate([kv[..., :s.q_nope],
                         jnp.broadcast_to(k_pe, (T, H, s.q_rope))], -1)
    v = kv[..., s.q_nope:]
    if fp8:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, 0)
    x = x + mm(_attend(q, k, v, fp8), t["wo"], fp8)
    m = rms(x, t["ffn_ln"], s.norm_eps)
    if "router" not in t:                   # a leading dense layer
        return x + _swiglu(m, t["w1"], t["w3"], t["w2"], fp8)
    scores = jax.nn.sigmoid(mm(m, t["router"], fp8))          # (T, E)
    _, idx = jax.lax.top_k(scores + t["bias"], s.top_k)
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / gates.sum(-1, keepdims=True) * s.routed_scale
    x = x + _swiglu(m, t["sw1"], t["sw3"], t["sw2"], fp8)
    # each held expert's gate per token (0 where the token did not pick
    # it), then the held experts one at a time
    held = jnp.arange(s.expert_first, s.expert_first + s.n_held)
    g = jnp.sum(jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0),
                1)

    def expert(x, w):
        w1, w3, w2, ge = w
        return x + ge[:, None] * _swiglu(m, w1, w3, w2, fp8), None

    x, _ = jax.lax.scan(expert, x, (t["w1"], t["w3"], t["w2"], g.T))
    return x


@partial(jax.jit, static_argnames=("shape", "dense"))
def _layer_weights(root, layer, *, shape: Shape, dense: bool):
    # through ``lax.map`` as ``program_params`` makes them, so that the
    # compiler emits the same arithmetic for both
    make = dense_tensors if dense else expert_tensors
    one = jax.lax.map(lambda l: make(shape, root, l), jnp.reshape(layer, (1,)))
    return jax.tree.map(lambda a: a[0].astype(jnp.float32), one)


def layer_weights(shape: Shape, root: jax.Array, index: int) -> dict:
    """Layer ``index``'s tensors in float32, made again from the seed."""
    return _layer_weights(root, jnp.uint32(index), shape=shape,
                          dense=index < shape.first_dense)


def layer(x, t, index: int, *, shape: Shape, fp8: bool):
    """Layer ``index`` over a (T, D) sequence (its tensors say its kind).
    The sequence runs padded to the model's context (a causal pass: the
    padding changes no earlier row), so that one compiled program of each
    layer kind serves every sequence of a run."""
    T, C = x.shape[0], -(-shape.context // Q_BLOCK) * Q_BLOCK
    if T > C:
        raise ValueError(f"{T} positions exceed the context, {C}")
    xp = jnp.pad(x, ((0, C - T), (0, 0)))
    return _layer(xp, t, shape=shape, fp8=fp8)[:T]


# ---- least operations and bytes ---------------------------------------------

def _attn_elems(s: Shape) -> int:
    """Matrix elements of one layer's MLA: q, kv_a, kv_b (applied once per
    token, expanded in prefill, absorbed into q and the context in
    decode) and o."""
    D, H = s.d_model, s.n_heads
    return (D * H * s.qk_head + D * (s.kv_rank + s.q_rope)
            + s.kv_rank * H * (s.q_nope + s.v_head) + H * s.v_head * D)


def _expert_elems(s: Shape) -> int:
    return 3 * s.d_model * s.d_ff_expert


def _fixed_elems(s: Shape) -> int:
    """Elements every token reads in every layer whatever it is routed
    to: attention, the dense FFN or the router and the shared expert."""
    D = s.d_model
    n_moe = s.n_layers - s.first_dense
    return (s.n_layers * _attn_elems(s) + s.first_dense * 3 * D * s.d_ff
            + n_moe * (D * s.n_experts + s.n_shared * _expert_elems(s)))


def _held_picks(s: Shape) -> float:
    """Expected token-expert pairs per token per layer that fall to the
    held experts under uniform routing."""
    return s.top_k * s.n_held / s.n_experts


def _touched(s: Shape, tokens: int) -> float:
    """Expected held experts that ``tokens`` tokens touch in one layer
    under uniform routing: n_held · (1 − (1 − top_k/E)^tokens)."""
    return s.n_held * (1.0 - (1.0 - s.top_k / s.n_experts) ** tokens)


def _token_flops(s: Shape) -> float:
    n_moe = s.n_layers - s.first_dense
    return 2 * (_fixed_elems(s)
                + n_moe * _held_picks(s) * _expert_elems(s))


def _step_weight_bytes(s: Shape, tokens: int) -> float:
    """Weights a pass over ``tokens`` tokens must read: everything but the
    held experts, and of those the ones its tokens touch."""
    n_moe = s.n_layers - s.first_dense
    return BYTES * (_fixed_elems(s)
                    + n_moe * _touched(s, tokens) * _expert_elems(s))


def weight_bytes(s: Shape) -> int:
    """Bytes of every weight held: the layers with all their held experts,
    and the output head."""
    n_moe = s.n_layers - s.first_dense
    return BYTES * (_fixed_elems(s) + n_moe * s.n_held * _expert_elems(s)
                    + s.d_model * s.vocab_size)


def _kv_bytes(s: Shape, tokens: int) -> int:
    return tokens * s.kv_bytes_per_token


def prefill_chunk(s: Shape, lo: int, hi: int, last: bool) -> tuple[int, int]:
    """(flops, bytes) of a chunk extending a latent cache of ``lo``
    positions by ``lo .. hi-1``, in the expanded form: each cached
    position's ``c_kv`` is expanded through ``kv_b`` once, and each query
    attends with (nope + rope) score dims and ``v_head`` value dims the
    positions it may see; the held experts by the expected picks and
    touches of T tokens under uniform routing."""
    T = hi - lo
    H = s.n_heads
    flops = T * _token_flops(s)
    flops += 2 * s.n_layers * lo * s.kv_rank * H * (s.q_nope + s.v_head)
    pairs = T * lo + T * (T + 1) // 2
    flops += 2 * s.n_layers * H * (s.qk_head + s.v_head) * pairs
    nbytes = _step_weight_bytes(s, T)
    nbytes += _kv_bytes(s, lo) + _kv_bytes(s, T)     # read past, write new
    nbytes += 2 * BYTES * T * s.d_model              # embedded rows in, out
    if last:
        flops += 2 * s.d_model * s.vocab_size
        nbytes += BYTES * s.d_model * s.vocab_size
    return int(flops), int(nbytes)


def decode_step(s: Shape, seq_lens: list[int]) -> tuple[int, int]:
    """(flops, bytes) of one decode step over the active slots (caches of
    ``seq_lens`` positions before it), absorbed: per visible position each
    head scores the latent ``c_kv`` and ``k_pe`` and sums its latent
    context. The least work: attention, the shared expert and the router
    whole, and the held experts by their expected number touched under
    uniform routing, n_held · (1 − (1 − top_k/E)^B) of ``n_held`` (at
    Moonlight's 6 of 64, 8 · (1 − (58/64)^B)); one logit row per slot."""
    B = len(seq_lens)
    H = s.n_heads
    flops = B * (_token_flops(s) + 2 * s.d_model * s.vocab_size)
    flops += sum(2 * s.n_layers * H * (2 * s.kv_rank + s.q_rope) * (n + 1)
                 for n in seq_lens)
    nbytes = _step_weight_bytes(s, B) + BYTES * s.d_model * s.vocab_size
    nbytes += sum(_kv_bytes(s, n) for n in seq_lens) + _kv_bytes(s, B)
    nbytes += 2 * BYTES * B * s.d_model
    return int(flops), int(nbytes)


# ---- the host pool ----------------------------------------------------------

def host_kv(cfg, tokens: int):
    """Zero ``c_kv`` and ``k_pe`` of ``tokens`` positions, (layers, tokens,
    width) each, as ``HostKVPool.put`` takes them."""
    (wk,), (wv,) = cfg.kv_widths
    return (np.zeros((cfg.n_layers, tokens, wk), ml_dtypes.bfloat16),
            np.zeros((cfg.n_layers, tokens, wv), ml_dtypes.bfloat16))
