"""The DeepSeek-V3 architecture (``bench/arch/deepseek_v3.py``) through the
whole harness at a tiny size on the CPU: served tokens pass the check, the
fp8 control and a planted routing fault do not; and its counts of
operations and bytes, checked by hand at one shape.

The tiny configuration holds 15 of its 16 experts. Where the bf16 program
and the float32 reference pick different experts at a near-tie between an
expert held here and one held elsewhere, a whole gated term is there in
one and not in the other; at two expert layers of width 128 one such
flip moves logits as far as the fp8 control does (4 of 16 held: sound
runs read 0.1-1.3 against the control's 1.6-3.1). With one expert held
elsewhere such flips are rare (0.01-0.07, control 1.0-1.7, reversed
router 0.4-0.8 over three seeds), and the check tells them apart."""
import json
import time
from pathlib import Path

import pytest

from bench import model
from bench.harness import run_cell

DATA = Path(__file__).resolve().parent / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CONFIG = DATA / "tiny_deepseek_v3.json"


def _run(seed: int, control=False, fault=None) -> dict:
    cell = json.loads((DATA / "tiny.sessions.json").read_text())
    cell.update(name="tiny.sessions", config=CONFIG.stem, traffic="sessions",
                chips=1, per_layer=[])
    return run_cell(cell, seed=seed, seconds=6.0, trace=False, device=CPU,
                    control=control, t_process=time.monotonic(),
                    log=lambda m: None, config_path=CONFIG, fault=fault)


def _reverse_router(pw, dw):
    """The router's expert columns reversed in the program's weights."""
    for w in (pw, dw):
        moe = w.params["moe"]
        w.params = dict(w.params,
                        moe=dict(moe, router=moe["router"][..., ::-1]))


def test_served_tokens_pass_and_the_control_does_not():
    r = _run(seed=2**31 + 29)
    c = r["check"]
    assert r["correct"], c
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["checked_tokens"]["value"] > 100
    rc = _run(seed=2**31 + 29, control=True)
    assert not rc["correct"], rc["check"]
    assert rc["check"]["max_logit_gap"]["value"] > \
        rc["check"]["max_logit_gap"]["limit"]


def test_reversed_router_is_not_correct():
    r = _run(seed=2**31 + 31, fault=_reverse_router)
    assert not r["correct"], r["check"]
    assert r["check"]["max_logit_gap"]["value"] > \
        r["check"]["max_logit_gap"]["limit"]


@pytest.fixture(scope="module")
def arch():
    return model.load_config("tiny", path=CONFIG)


def test_counts_by_hand(arch):
    """D 128, 4 heads of 32 + 16 (q, k) and 32 (v), rank 32, 3 layers
    (1 dense of width 256), router 16, 15 held of width 64, top 4, 2
    shared, vocab 500."""
    mod, s = arch
    attn = 128 * 4 * 48 + 128 * 48 + 32 * 4 * 64 + 4 * 32 * 128
    assert attn == 55296
    fixed = 3 * attn + 3 * 128 * 256 + 2 * (128 * 16 + 2 * 3 * 128 * 64)
    expert = 3 * 128 * 64
    token_flops = 2 * (fixed + 2 * (4 * 15 / 16) * expert)
    head = 128 * 500
    # one decode step of two slots holding 10 and 3 positions: absorbed
    # attention, 3 * 4 heads * (2 * 32 + 16) MACs per visible position
    touched = 15 * (1 - (12 / 16) ** 2)
    flops, nbytes = mod.decode_step(s, [10, 3])
    assert flops == int(2 * (token_flops + 2 * head)
                        + 2 * 3 * 4 * 80 * (11 + 4))
    kv = 3 * (32 + 16) * 2
    assert nbytes == int(2 * (fixed + 2 * touched * expert) + 2 * head
                         + kv * (10 + 3 + 2) + 2 * 2 * 2 * 128)
    # a chunk of 4 over 6 cached positions, the last: the cached c_kv
    # expanded once, 4 * 6 + 10 visible pairs at 48 + 32 dims a head
    touched = 15 * (1 - (12 / 16) ** 4)
    flops, nbytes = mod.prefill_chunk(s, 6, 10, True)
    assert flops == int(4 * token_flops + 2 * 3 * 6 * 32 * 4 * 64
                        + 2 * 3 * 4 * 80 * 34 + 2 * head)
    assert nbytes == int(2 * (fixed + 2 * touched * expert)
                         + kv * 10 + 2 * 2 * 4 * 128 + 2 * head)
    assert mod.weight_bytes(s) == 2 * (fixed + 2 * 15 * expert + head)


def test_host_kv_is_the_latent_pair(arch):
    mod, s = arch
    k, v = mod.host_kv(mod.program_config(s), 512)
    assert k.shape == (3, 512, 32) and v.shape == (3, 512, 16)
    assert not k.any() and not v.any()
