"""A configuration file, read into the shapes the benchmark works with.

``bench/configs/<name>.json`` holds the published ``config.json`` keys as
they are run, and may name its architecture (``"architecture"``, default
``"dense"``). The architecture is one file, ``bench/arch/<arch>.py``,
loaded by path, that owns everything the architecture decides:

- ``load(name, raw) -> shape``: a frozen, hashable shape (a jit static
  argument) with at least ``name``, ``n_layers``, ``d_model``,
  ``vocab_size``, ``norm_eps`` and ``tie_embeddings``;
- ``program_config(shape)``: the program's ``ModelConfig``;
- ``program_params(shape, seed, padded_vocab)``: the program's parameter
  tree, made on the device;
- ``layer_weights(shape, root, index)`` and
  ``layer(x, t, index, *, shape, fp8)``: one layer of the plain reference,
  told its index, so that stacks mixing layer kinds fit;
- ``prefill_chunk(shape, lo, hi, last)``, ``decode_step(shape, seq_lens)``
  and ``weight_bytes(shape)``: the least operations and bytes
  (``bench/flops.py`` says what is counted);
- ``host_kv(cfg, tokens)``: zero K and V of ``tokens`` positions as the
  program's host pool stores them (the warm-up's prefix hits).
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ARCH_DIR = BENCH / "arch"


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The module in the file at ``path``, loaded once per process, so that
    its jitted functions and its shape class are the same for every
    caller."""
    if not path.is_file():
        raise FileNotFoundError(f"no architecture file {path}")
    name = f"bench_arch_{path.stem}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, path=None):
    """(architecture module, shape) of ``bench/configs/<name>.json`` (or of
    the file at ``path``)."""
    path = Path(path) if path else BENCH / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    arch = load_module(ARCH_DIR / f"{raw.get('architecture', 'dense')}.py")
    return arch, arch.load(name, raw)
