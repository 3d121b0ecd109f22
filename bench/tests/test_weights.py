"""The weights made all at once for the program equal those the
reference makes one layer at a time."""
from pathlib import Path

import numpy as np

from bench import reference, weights
from bench.model import load_config

TINY = Path(__file__).resolve().parent / "data" / "tiny.json"


def test_stacked_equals_per_layer_and_padding_is_zero():
    dense, s = load_config("tiny", path=TINY)
    rows = reference.padded_rows(s.vocab_size)
    p = dense.program_params(s, 2**32 + 3, rows)
    root = weights.root_key(2**32 + 3)
    for layer in range(s.n_layers):
        t = dense.layer_weights(s, root, layer)
        np.testing.assert_array_equal(np.asarray(p["attn"]["wq"][layer], np.float32),
                                      np.asarray(t["wq"], np.float32))
        np.testing.assert_array_equal(np.asarray(p["mlp"]["w2"][layer], np.float32),
                                      np.asarray(t["w2"], np.float32))
        np.testing.assert_array_equal(np.asarray(p["attn"]["bk"][layer], np.float32),
                                      np.asarray(t["bk"], np.float32))
    e = np.asarray(p["embed"], np.float32)
    assert e.shape == (rows, s.d_model) and rows == 512
    assert not e[s.vocab_size:].any() and e[:s.vocab_size].any()
    assert "lm_head" not in p                     # tied, as configured


def test_seeds_differ():
    dense, s = load_config("tiny", path=TINY)
    a = dense.program_params(s, 1, 512)["attn"]["wk"]
    b = dense.program_params(s, 2, 512)["attn"]["wk"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
