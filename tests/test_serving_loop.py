"""Serving loop: interleaved chunked prefill + continuous batching, the
capacity/shutdown bugfixes it depends on, and backpressure admission."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker)
from repro.serving.loop import ServingLoop
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest


def _req(rid, toks, max_new, **kw):
    return ServingRequest(req_id=rid, tokens=toks, max_new=max_new, **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(cfg, params, *, max_batch=4, max_len=512, n_pages=None,
        n_workers=2, chunk=64):
    n_pages = n_pages or 1 + (max_batch + 2) * (max_len // 64)
    pp = DevicePagePool(cfg, n_pages=n_pages, page_tokens=64)
    pool = HostKVPool()
    pws = [PrefillWorker(params, cfg, pool, prefill_chunk=chunk,
                         page_pool=pp) for _ in range(n_workers)]
    dw = DecodeWorker(params, cfg, max_batch=max_batch, max_len=max_len,
                      substrate="paged", page_pool=pp)
    return pws, dw, pp


def _oracle(cfg, params, reqs, max_new, max_len=512):
    """Request-at-a-time reference streams (fresh engines, one at a time)."""
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=64)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=max_len)
    out = {}
    for rid, toks in reqs.items():
        res = pw(toks)
        dw.join(_req(rid, toks, max_new), res)
        seq = [res.first_token]
        while dw.n_active:
            for r, tok, fin in dw.step():
                seq.append(tok)
        out[rid] = seq
    return out


# ---------------------------------------------------------------------------
# bugfix regressions
# ---------------------------------------------------------------------------

def test_join_full_batch_raises_runtime_error(setup):
    """A full decode batch must raise RuntimeError from join — the old
    bare StopIteration (from next() on an exhausted generator expression)
    is swallowed as silent termination inside any driver generator."""
    cfg, params = setup
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=64)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=512)
    rng = np.random.default_rng(0)
    t1 = rng.integers(0, cfg.vocab_size, 80)
    r1 = pw(t1)
    dw.join(_req(0, t1, 4), r1)
    assert not dw.has_free_slot and dw.free_slots == 0
    t2 = rng.integers(0, cfg.vocab_size, 80)
    r2 = pw(t2)

    with pytest.raises(RuntimeError, match="decode batch full"):
        dw.join(_req(1, t2, 4), r2)

    # the failure mode the bug produced: inside a generator, StopIteration
    # silently ENDS iteration; RuntimeError propagates (PEP 479 makes the
    # raw StopIteration a RuntimeError too, but with a misleading message
    # — the explicit raise is load-bearing for real drivers)
    def driver():
        yield "before"
        dw.join(_req(1, t2, 4), r2)
        yield "after"

    g = driver()
    assert next(g) == "before"
    with pytest.raises(RuntimeError, match="decode batch full"):
        next(g)
    r2.release_pages()


def test_join_overlong_rejects_identically_on_both_substrates(setup):
    """Dense .at[].set past max_len is silently dropped on CPU → wrong
    tokens; the paged branch already rejected. Both substrates must now
    reject an overlong request with the same error."""
    cfg, params = setup
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=64)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, 100)
    res = pw(toks)

    msgs = {}
    for substrate in ("paged", "dense"):
        dw = DecodeWorker(params, cfg, max_batch=2, max_len=128,
                          substrate=substrate)
        with pytest.raises(ValueError) as ei:
            dw.join(_req(0, toks, 64), res)  # 100 + 64 > 128
        msgs[substrate] = str(ei.value)
        assert dw.n_active == 0              # nothing was admitted
    assert msgs["paged"] == msgs["dense"]
    assert "exceeds max_len" in msgs["paged"]
    res.release_pages()


def test_prefetcher_fetch_after_close_fails_fast(tmp_path):
    """fetch() after close() used to enqueue onto a dead thread and hang
    wait() forever; now the handle fails immediately."""
    from repro.serving.ssd_store import AsyncPrefetcher, SSDBlockStore
    store = SSDBlockStore(str(tmp_path), writeback_batch=1)
    k = np.zeros((2, 8, 1, 4), np.float32)
    store.put(7, k, k)
    store.flush()
    pf = AsyncPrefetcher(store)
    pf.close()
    assert not pf._thread.is_alive()

    h = pf.fetch([7])
    assert h.wait(timeout=1.0)               # pre-fix: hangs forever
    assert 7 in h.failed and h.result(7) is None
    pf.close()                               # idempotent
    store.close()


def test_prefetcher_close_drains_deterministically(tmp_path):
    """close() must join the worker thread (no 2s-timeout leak) even with
    a deep pending queue; in-flight handles complete as failures rather
    than hanging."""
    from repro.serving.ssd_store import AsyncPrefetcher, SSDBlockStore
    store = SSDBlockStore(str(tmp_path), writeback_batch=1)
    k = np.zeros((4, 128, 2, 16), np.float32)
    keys = list(range(40))
    for key in keys:
        store.put(key, k, k)
    store.flush()
    pf = AsyncPrefetcher(store)
    handles = [pf.fetch(keys) for _ in range(4)]   # deep layer-major queue
    pf.close()
    assert not pf._thread.is_alive()               # actually joined
    for h in handles:
        assert h.wait(timeout=5.0)                 # all delivered or failed
    store.close()


# ---------------------------------------------------------------------------
# chunk-resumable prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_resumable_matches_blocking(setup):
    cfg, params = setup
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=64)
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, 300)

    cp = pw.start(toks)
    n = 0
    while not cp.advance():
        n += 1
    assert cp.done and cp.chunks_done == n + 1
    assert cp.chunks_done == -(-300 // 64)       # ceil: one advance per chunk

    pool2 = HostKVPool()
    pw2 = PrefillWorker(params, cfg, pool2, prefill_chunk=64)
    ref = pw2(toks)
    assert cp.result.first_token == ref.first_token
    np.testing.assert_array_equal(cp.result.host_kv()[0], ref.host_kv()[0])


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

def test_loop_mixed_load_bit_exact_with_thread_fed_arrivals(setup):
    """Sustained mixed load: arrivals land WHILE decodes run; every
    emitted stream must equal the request-at-a-time oracle, and shutdown
    must leave the page pool leak-free."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=16)
    rng = np.random.default_rng(3)
    reqs = {i: rng.integers(0, cfg.vocab_size, int(rng.integers(80, 300)))
            for i in range(6)}

    def feeder():
        for i, t in reqs.items():
            while not loop.submit(_req(i, t, 5)):
                time.sleep(0.01)             # shed → retry (test wants all 6)
            time.sleep(0.005)
        loop.close_intake()

    th = threading.Thread(target=feeder, name="repro-loop-feeder")
    th.start()
    stats = loop.run()
    th.join()

    assert stats["completed"] == 6
    oracle = _oracle(cfg, params, reqs, max_new=5)
    for i in reqs:
        assert loop.outputs[i].done
        assert loop.outputs[i].tokens == oracle[i], f"req {i} diverged"
    pp.check_leaks()                         # clean shutdown, nothing pinned
    assert stats["tbt_n"] > 0 and stats["tbt_p99_s"] >= stats["tbt_p50_s"]


@pytest.mark.parametrize("stage", ["device", "refused"])
def test_loop_tokens_whether_prefills_stage_on_device_or_not(setup, stage):
    """Prefills that stage their pages straight from the device, and
    prefills whose stage the guard refuses (the whole cache read back,
    staged later by the join through the host path), serve the
    request-at-a-time oracle's tokens."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params, max_len=1024, n_pages=1 + 6 * 16,
                      n_workers=1, chunk=128)
    loop = ServingLoop(pws, dw, chunks_per_iter=1)
    if stage == "refused":
        pws[0].stage_guard = lambda n_pages: False
    rng = np.random.default_rng(14)
    shared = rng.integers(0, cfg.vocab_size, 512)
    reqs = {i: np.concatenate([shared, rng.integers(0, cfg.vocab_size,
                                                    60 + 150 * i)])
            for i in range(3)}
    reqs[3] = rng.integers(0, cfg.vocab_size, 700)
    for i, t in reqs.items():
        assert loop.submit(_req(i, t, 5))
    while not loop.idle:
        loop.iterate()
    oracle = _oracle(cfg, params, reqs, max_new=5, max_len=1024)
    for i in reqs:
        assert loop.outputs[i].tokens == oracle[i], f"req {i} diverged"
    st, dst = pws[0].stats(), dw.stats()
    n = len(reqs)
    if stage == "device":
        assert (st["device_stages"], st["host_stages"]) == (n, 0)
        assert dst["zero_copy_joins"] == n
    else:
        assert (st["device_stages"], st["host_stages"]) == (0, n)
        assert dst["staged_joins"] == n
    pp.check_leaks()


def test_loop_interleaves_prefill_chunks_between_decode_steps(setup):
    """Deterministic mode: while a long prefill is mid-chunks, active
    decode slots must keep emitting — the chunk interleave is visible as
    decode steps strictly interleaved with prefill chunks."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params, n_workers=1)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=16)
    rng = np.random.default_rng(4)
    short = rng.integers(0, cfg.vocab_size, 80)      # 2 chunks
    long = rng.integers(0, cfg.vocab_size, 448)      # 7 chunks

    assert loop.submit(_req(0, short, 12))
    # let the short request join and start decoding
    while loop.stats()["joined"] == 0:
        loop.iterate()
    steps_before = loop.stats()["decode_steps"]
    assert loop.submit(_req(1, long, 3))
    # drive until the long prefill finishes its chunks
    while loop.stats()["joined"] < 2:
        loop.iterate()
    steps_during = loop.stats()["decode_steps"] - steps_before
    # 7 prefill chunks at 1 chunk/iteration → ≥ 6 decode iterations ran
    # while the long prefill was suspended mid-chunks
    assert steps_during >= 6
    assert len(loop.outputs[0].tokens) > 6   # slot 0 kept emitting
    loop.close_intake()
    loop.run()
    oracle = _oracle(cfg, params, {0: short, 1: long}, max_new=12)
    assert loop.outputs[0].tokens == oracle[0][:12]
    pp.check_leaks()


def test_loop_backpressure_sheds_and_recovers(setup):
    """submit() must shed when the queue saturates (hard cap) and admit
    again once the loop drains; a shed request never consumes compute."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params, max_batch=2)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=2)
    rng = np.random.default_rng(5)
    toks = [rng.integers(0, cfg.vocab_size, 100) for _ in range(6)]

    accepted = [loop.submit(_req(i, t, 3)) for i, t in enumerate(toks)]
    assert accepted[:2] == [True, True]
    assert not all(accepted), "hard queue cap never triggered"
    n_acc = sum(accepted)
    assert loop.stats()["rejected"] == 6 - n_acc
    chunks_before = loop.stats()["prefill_chunks"]
    assert chunks_before == 0                # rejected ⇒ nothing ran

    # drain, then the loop must admit again
    loop.close_intake()
    loop.run()
    assert loop.stats()["completed"] == n_acc
    pp.check_leaks()


def test_loop_full_batch_defers_joins_until_slots_free(setup):
    """More concurrent requests than decode slots: the loop must hold
    finished prefills in pending-join (no RuntimeError from join) and
    complete everything as slots recycle."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params, max_batch=2)
    loop = ServingLoop(pws, dw, chunks_per_iter=2, max_queue=16)
    rng = np.random.default_rng(6)
    reqs = {i: rng.integers(0, cfg.vocab_size, 100) for i in range(5)}
    for i, t in reqs.items():
        assert loop.submit(_req(i, t, 4))
    loop.close_intake()
    stats = loop.run()
    assert stats["completed"] == 5
    oracle = _oracle(cfg, params, reqs, max_new=4)
    for i in reqs:
        assert loop.outputs[i].tokens == oracle[i][:4]
    pp.check_leaks()


def test_loop_tight_pool_defers_joins_instead_of_mid_decode_oom(setup):
    """A join that eats the last free pages OOMs a decode step a few
    iterations later (page growth of active slots can't allocate).
    The loop must hold the join back until headroom covers every active
    slot's worst-case growth — all requests still complete."""
    cfg, params = setup
    # barely two sequences of pages: pending joins pin staged runs while
    # two slots decode
    pws, dw, pp = _mk(cfg, params, max_batch=2, max_len=455, n_pages=15,
                      n_workers=1, chunk=64)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=16)
    rng = np.random.default_rng(9)
    reqs = {i: rng.integers(0, cfg.vocab_size, 256 if i % 2 else 384)
            for i in range(6)}
    for i, t in reqs.items():
        assert loop.submit(_req(i, t, 7 if i % 2 else 3))
    loop.close_intake()
    stats = loop.run()                       # pre-fix: MemoryError mid-step
    assert stats["completed"] == 6
    assert stats["join_oom"] > 0             # the guard actually engaged
    pp.check_leaks()


def test_loop_stop_releases_pending_work(setup):
    """stop() mid-flight: queued and mid-prefill work is abandoned, page
    references of never-joined results are dropped (leak check green)."""
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=16)
    rng = np.random.default_rng(7)
    for i in range(4):
        loop.submit(_req(i, rng.integers(0, cfg.vocab_size, 200), 8))
    for _ in range(3):                       # partial progress
        loop.iterate()
    loop.stop()
    loop.run()
    assert dw.n_active == 0
    pp.check_leaks()


def test_backpressure_signal_policy_semantics():
    """Engine-side loads mirror §7: baseline is stage-local (blind to
    decode), early sees current occupancy but not in-flight prefills,
    predictive counts them — the information-lag fix."""
    from repro.core.policies.admission import BackpressureSignal
    from repro.core.policies.base import get_policy

    base = get_policy("admission", "baseline")
    early = get_policy("admission", "early")
    pred = get_policy("admission", "predictive")

    # decode saturated + heavy in-flight prefill, but the queue is empty
    sig = BackpressureSignal(queue_depth=0, queue_capacity=8,
                             slots_used=4, slots_total=4,
                             prefills_active=4,
                             pages_pinned=10, pages_total=100)
    assert base.engine_load(sig) == 0.0          # stage-local blindness
    assert early.engine_load(sig) == pytest.approx(4 / 12)
    assert pred.engine_load(sig) == pytest.approx(8 / 12)
    assert base.engine_admit(sig)
    assert not pred.engine_admit(sig, priority=0) or \
        pred.engine_load(sig) <= pred.base_limit
    # priority buys headroom (§10)
    sig2 = BackpressureSignal(queue_depth=8, queue_capacity=8,
                              slots_used=4, slots_total=4)
    assert not early.engine_admit(sig2, priority=0)
    assert early.engine_admit(sig2, priority=1)

    # pinned pages alone must trip the pool-occupancy path
    sig3 = BackpressureSignal(queue_depth=0, queue_capacity=8,
                              slots_used=1, slots_total=4,
                              pages_pinned=95, pages_total=100)
    assert early.engine_load(sig3) == pytest.approx(0.95)
    assert not early.engine_admit(sig3)

    # spilled victims are commitments only the predictive view counts: a
    # slot freed by preemption is NOT free capacity — the victim claims
    # it back at restore
    sig4 = BackpressureSignal(queue_depth=0, queue_capacity=8,
                              slots_used=2, slots_total=4, spilled=4)
    assert early.engine_load(sig4) == pytest.approx(2 / 12)
    assert pred.engine_load(sig4) == pytest.approx(6 / 12)
    assert sig4.committed_frac(include_prefills=True,
                               include_spilled=True) > \
        sig4.committed_frac(include_prefills=True)


def test_page_pool_pressure_distinguishes_pinned_from_evictable(setup):
    cfg, params = setup
    pws, dw, pp = _mk(cfg, params, max_batch=2, max_len=640, n_workers=1)
    rng = np.random.default_rng(8)
    toks = rng.integers(0, cfg.vocab_size, 512)
    res = pws[0](toks)                       # one full block
    p = pp.pressure()
    assert p["capacity"] == pp.n_pages - 1
    assert p["used"] == p["pinned"] + p["evictable"]
    assert p["pinned"] > 0                   # the staged (unjoined) run
    dw.join(_req(0, toks, 2), res)
    while dw.n_active:
        dw.step()
    p2 = pp.pressure()
    # slot done: registered full blocks remain but are registry-only now
    assert p2["pinned"] < p["pinned"]
    assert p2["evictable"] > 0
    assert 0.0 <= p2["pinned_frac"] <= p2["occupancy"] <= 1.0
