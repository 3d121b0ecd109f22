"""The generator: the same seed gives the same inputs, and every seed the
same schedule with other tokens."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

BENCH = Path(__file__).resolve().parents[1]


def _cell(name):
    return json.loads((BENCH / "cells" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix,cell", [("sessions", "qwen2_5_3b.sessions"),
                                      ("chat", "phi3_mini_4k.chat"),
                                      ("cold", "qwen2_5_3b.cold")])
def test_same_seed_same_inputs_and_seeds_share_the_schedule(mix, cell):
    m, c = traffic.load_mix(mix), _cell(cell)
    big = 2**33 + 5
    a = traffic.make_workload(m, c, big, 51, 32000)
    b = traffic.make_workload(m, c, big, 51, 32000)
    other = traffic.make_workload(m, c, 3, 51, 32000)
    ra = a.requests or [r for cl in a.clients for r in cl]
    rb = b.requests or [r for cl in b.clients for r in cl]
    assert all(np.array_equal(x.tokens, y.tokens) and x.due_s == y.due_s
               for x, y in zip(ra, rb))
    ro = other.requests or [r for cl in other.clients for r in cl]
    # another seed: other tokens, the same sizes at the same times
    assert [(len(x.tokens), x.max_new, x.due_s) for x in ra] == \
        [(len(y.tokens), y.max_new, y.due_s) for y in ro]
    assert not all(np.array_equal(x.tokens, y.tokens) for x, y in zip(ra, ro))
    assert a.prompt_lengths == other.prompt_lengths


def test_sessions_share_prefixes():
    c = _cell("qwen2_5_3b.sessions")
    w = traffic.make_workload(traffic.load_mix("sessions"), c, 1, 51, 32000)
    blocks = [tuple(r.tokens[i:i + 512]) for r in w.requests
              for i in range(0, len(r.tokens), 512)]
    assert len(set(blocks)) < len(blocks)
    assert all(len(r.tokens) % 512 == 0 and len(r.tokens) <= 7680
               and len(r.tokens) + r.max_new <= 8192 for r in w.requests)
    assert all(0 <= d < S for S in w.prompt_lengths
               for d in w.hit_depths[S])
    assert any(d > 0 for S in w.prompt_lengths for d in w.hit_depths[S])


def test_cold_shares_nothing_on_the_sessions_schedule():
    c = _cell("qwen2_5_3b.cold")
    cold = traffic.make_workload(traffic.load_mix("cold"), c, 2**33 + 1, 51,
                                 151936)
    warm = traffic.make_workload(traffic.load_mix("sessions"), c, 2**33 + 1,
                                 51, 151936)
    assert [(len(r.tokens), r.max_new, r.due_s) for r in cold.requests] == \
        [(len(r.tokens), r.max_new, r.due_s) for r in warm.requests]
    # each request's blocks (the last one cut to the prompt) are its own
    owner = {}
    for r in cold.requests:
        for i in range(0, len(r.tokens), 512):
            b = tuple(r.tokens[i:i + 512])
            assert owner.setdefault(b, r.req_id) == r.req_id
    assert cold.hit_depths == {S: [0] for S in cold.prompt_lengths}
    assert any(d > 0 for d in sum(warm.hit_depths.values(), []))


def test_chat_prompts_and_session_bound():
    c = _cell("phi3_mini_4k.chat")
    w = traffic.make_workload(traffic.load_mix("chat"), c, 1, 51, 32064)
    assert len(w.clients) == c["clients"]
    reqs = [r for cl in w.clients for r in cl]
    assert set(w.prompt_lengths) <= {640, 768, 896}
    assert all(len(r.tokens) + r.max_new <= 1024 for r in reqs)
    # every prompt starts with one of the 4 hot system blocks
    assert len({tuple(r.tokens[:512]) for r in reqs}) <= 4
