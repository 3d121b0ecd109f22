"""The dense architecture, bit for bit as it was recorded on the CPU before
its code moved into ``bench/arch/dense.py``: a checksum of every leaf of
the program's parameter tree at two seeds, the reference's logit gaps
(served and control) for three fixed prompts and outputs, and the counts
of operations and bytes of both published configurations over a grid of
chunks and steps (``data/dense_golden.json``)."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import reference
from bench.model import load_config

DATA = Path(__file__).resolve().parent / "data"
GOLD = json.loads((DATA / "dense_golden.json").read_text())


def _leaf_sums(params) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            str(a.dtype).encode() + str(a.shape).encode()
            + a.tobytes()).hexdigest()
    return out


@pytest.mark.parametrize("seed", sorted(GOLD["weights"]))
def test_weights(seed):
    dense, s = load_config("tiny", path=DATA / "tiny.json")
    p = dense.program_params(s, int(seed), reference.padded_rows(s.vocab_size))
    assert _leaf_sums(p) == GOLD["weights"][seed]


def test_reference_gaps():
    g = GOLD["gaps"]
    dense, s = load_config("tiny", path=DATA / "tiny.json")
    gaps = reference.served_gaps(
        dense, s, g["seed"], [np.asarray(p, np.int32) for p in g["prompts"]],
        [np.asarray(o, np.int64) for o in g["served_tokens"]], control=True)
    for k in ("served", "control"):
        assert [a.tolist() for a in gaps[k]] == g[k]


@pytest.mark.parametrize("name", sorted(GOLD["counts"]))
def test_counts(name):
    dense, s = load_config(name)
    c = GOLD["counts"][name]
    assert dense.weight_bytes(s) == c["weight_bytes"]
    assert [[lo, hi, last, *dense.prefill_chunk(s, lo, hi, last)]
            for lo, hi, last, _, _ in c["prefill_chunk"]] == c["prefill_chunk"]
    assert [[sl, *dense.decode_step(s, sl)]
            for sl, _, _ in c["decode_step"]] == c["decode_step"]
