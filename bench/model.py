"""A configuration file, read into the shapes the benchmark works with.

``bench/configs/<name>.json`` holds the published ``config.json`` keys as
they are run. ``Shape`` is what the yardstick (weights, reference, FLOP
and byte counts) reads; ``program_config`` turns it into the program's own
``ModelConfig`` for the system under test.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Shape:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over every layer."""
        return 2 * 2 * self.n_layers * self.n_kv_heads * self.head_dim


def load_config(name: str, path=None) -> tuple[Shape, dict]:
    """(shape, the file's whole content) of ``bench/configs/<name>.json``
    (or of the file at ``path``)."""
    path = Path(path) if path else BENCH / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    assumed = raw.get("assumed", {})
    heads = raw["num_attention_heads"]
    shape = Shape(
        name=name,
        n_layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"],
        n_heads=heads,
        n_kv_heads=raw["num_key_value_heads"],
        head_dim=raw.get("head_dim", assumed.get(
            "head_dim", raw["hidden_size"] // heads)),
        d_ff=raw["intermediate_size"],
        vocab_size=raw["vocab_size"],
        qkv_bias=bool(raw.get("attention_bias",
                              assumed.get("attention_bias", False))),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
    )
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: only SwiGLU (silu) MLPs are served")
    return shape, raw


def program_config(s: Shape):
    """The program's ``ModelConfig`` for these shapes."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(
        name=s.name, kind="dense", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_kv_heads, head_dim=s.head_dim,
        d_ff=s.d_ff, vocab_size=s.vocab_size, attn_bias=s.qkv_bias,
        rope_theta=s.rope_theta, norm_eps=s.norm_eps,
        tie_embeddings=s.tie_embeddings, remat=False)
    if cfg.padded_heads != s.n_heads:
        raise ValueError(f"{s.name}: the program would pad {s.n_heads} query "
                         f"heads to {cfg.padded_heads}; the weights here "
                         f"are made for unpadded heads")
    return cfg
