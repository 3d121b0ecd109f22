"""The serving engine's profiler spans (``repro.serving.tracing``), each
request's timeline stamps and the prefill worker's KV byte counters, on a
two-layer model: one request computed cold, then one that hits its first
block in the host pool."""
import glob

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.trace import BLOCK_TOKENS
from repro.models.transformer import init_params
from repro.serving.engine import DecodeWorker, HostKVPool, PrefillWorker
from repro.serving.loop import ServingLoop
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest
from repro.serving.tracing import span

PAGE = 64
S0, S1 = 600, 612           # S1 shares S0's first block
PARENT = {
    "loop.iteration": None,
    "loop.admit": "loop.iteration",
    "loop.join": "loop.iteration",
    "prefill.chunk": "loop.iteration",
    "decode.step": "loop.iteration",
    "prefill.lookup": "prefill.chunk",
    "prefill.load": "prefill.chunk",
    "prefill.compute": "prefill.chunk",
    "prefill.readback": "prefill.chunk",
    "prefill.store": "prefill.chunk",
    "prefill.stage": "prefill.chunk",
    "decode.prepare": "decode.step",
    "decode.launch": "decode.step",
    "decode.readout": "decode.step",
}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab_size, S0)
    prompts = [base, np.concatenate(
        [base[:BLOCK_TOKENS], rng.integers(0, cfg.vocab_size,
                                           S1 - BLOCK_TOKENS)])]
    return cfg, params, prompts


def _serve(cfg, params, prompts):
    """Both requests through a ``ServingLoop``, the second submitted once
    the first has its first token. Returns (loop, prefill worker)."""
    pp = DevicePagePool(cfg, n_pages=1 + 6 * 16, page_tokens=PAGE)
    pw = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=128,
                       page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                      substrate="paged", page_pool=pp)
    loop = ServingLoop([pw], dw, chunks_per_iter=1)
    assert loop.submit(ServingRequest(0, prompts[0], 6))
    while not (0 in loop.outputs and loop.outputs[0].tokens):
        loop.iterate()
    assert loop.submit(ServingRequest(1, prompts[1], 6))
    while not loop.idle:
        loop.iterate()
    return loop, pw


def _read_spans(logdir) -> list:
    """The ``serve.`` events of a trace, each with its parent: the
    innermost event enclosing it on the same host thread."""
    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = sorted((e for e in line.events
                          if e.name.startswith("serve.")),
                         key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []
            for e in evs:
                h = dict(name=e.name[len("serve."):], t0=e.start_ns,
                         t1=e.start_ns + e.duration_ns, info=dict(e.stats))
                while stack and stack[-1]["t1"] <= h["t0"]:
                    stack.pop()
                h["parent"] = stack[-1] if stack else None
                stack.append(h)
                spans.append(h)
    return spans


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    cfg, params, prompts = setup
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        loop, pw = _serve(cfg, params, prompts)
    return loop, pw, _read_spans(logdir)


@pytest.fixture(scope="module")
def untraced(setup):
    cfg, params, prompts = setup
    return _serve(cfg, params, prompts)


def test_span_outside_a_session_is_inert():
    s = span("decode.step", slots=3)
    assert not isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass


def test_tracing_leaves_every_served_token_unchanged(traced, untraced):
    a, b = traced[0].outputs, untraced[0].outputs
    assert sorted(a) == sorted(b) == [0, 1]
    for rid in a:
        assert a[rid].done and b[rid].done
        assert a[rid].tokens == b[rid].tokens and len(a[rid].tokens) == 6


def test_every_span_written_and_nested(traced):
    spans = traced[2]
    assert {h["name"] for h in spans} == set(PARENT)
    for h in spans:
        p = h["parent"]
        assert (p["name"] if p else None) == PARENT[h["name"]], h["name"]


def test_prefill_spans_carry_their_request(traced):
    spans = [h for h in traced[2] if h["name"].startswith("prefill.")]
    assert {h["info"]["req"] for h in spans} == {0, 1}
    for h in spans:
        if h["name"] != "prefill.chunk":
            assert h["info"]["req"] == h["parent"]["info"]["req"]
    for rid, S in ((0, S0), (1, S1)):
        comp = [h["info"] for h in spans
                if h["name"] == "prefill.compute" and h["info"]["req"] == rid]
        first = 0 if rid == 0 else BLOCK_TOKENS
        assert [c["lo"] for c in comp] == list(range(first, S, 128))
        assert comp[-1]["hi"] == S


@pytest.mark.parametrize("ssd_mode", ["blocking", "overlap"])
def test_no_prefill_span_spans_a_yield(setup, tmp_path, ssd_mode):
    """Every child of a prefill lies inside the one ``advance()`` that
    opened it, with at most one device chunk in each, on the path that
    loads host-pool hits and on the one that streams them off the SSD
    tier while recomputing the head."""
    cfg, params, prompts = setup
    pool = HostKVPool(capacity_blocks=1, ssd_capacity_blocks=32,
                      ssd_dir=str(tmp_path / "ssd"), writeback_batch=1)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=128,
                       ssd_mode=ssd_mode)
    pw(prompts[0])
    pw(prompts[0][::-1])            # demotes the first prompt's block
    pool.store.flush()
    with jax.profiler.trace(str(tmp_path / "trace")):
        cp = pw.start(prompts[1])
        cp.info["req"] = 1
        res = cp.drain()
    pool.close()
    assert res.reused_blocks == 1
    assert res.overlapped == (ssd_mode == "overlap")
    spans = _read_spans(tmp_path / "trace")
    chunks = [h for h in spans if h["name"] == "prefill.chunk"]
    assert len(chunks) == cp.chunks_done
    for h in spans:
        if h["name"] != "prefill.chunk":
            assert h["parent"]["name"] == "prefill.chunk", h["name"]
            assert h["parent"]["t0"] <= h["t0"] <= h["t1"] <= \
                h["parent"]["t1"]
    for c in chunks:
        assert sum(h["name"] == "prefill.compute" and h["parent"] is c
                   for h in spans) <= 1


@pytest.mark.parametrize("run", ["traced", "untraced"])
def test_request_timeline_stamps(run, request):
    loop = request.getfixturevalue(run)[0]
    for o in loop.outputs.values():
        assert o.t_submit <= o.t_first_chunk <= o.token_t[0]
        assert 0 < o.prefill_busy_s <= o.token_t[0] - o.t_first_chunk


@pytest.mark.parametrize("counter", ["h2d_bytes", "d2h_bytes", "put_bytes",
                                     "stage_bytes"])
def test_kv_byte_counters_match_shapes(setup, untraced, counter):
    cfg = setup[0]
    per_token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * \
        np.dtype(jax.numpy.bfloat16).itemsize
    pages = -(-S0 // PAGE) + -(-(S1 - BLOCK_TOKENS) // PAGE)
    tokens = {
        "h2d_bytes": BLOCK_TOKENS,          # the second prompt's hit
        # only what the host pool stores: both prompts staged their pages
        # from the device
        "d2h_bytes": BLOCK_TOKENS,
        "put_bytes": BLOCK_TOKENS,          # the first prompt's one block
        # the first prompt's pages, and the second's past the block it
        # adopts from the page registry
        "stage_bytes": pages * PAGE,
    }[counter]
    assert untraced[1].stats()[counter] == tokens * per_token
