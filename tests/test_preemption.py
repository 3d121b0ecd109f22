"""Decode preemption with victim spill to the host KV tier, the unified
ServingRequest/RequestOutput surface, the cross-component stats()
protocol, and the submit() queue-cap race fix."""
import threading
import warnings

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models.transformer import init_params
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  plan_restore)
from repro.serving.loop import ServingLoop
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import RequestOutput, ServingRequest


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("smollm-360m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(cfg, params, *, max_batch=4, max_len=512, n_pages=None,
        n_workers=1, chunk=64):
    n_pages = n_pages or 1 + (max_batch + 2) * (max_len // 64)
    pp = DevicePagePool(cfg, n_pages=n_pages, page_tokens=64)
    pool = HostKVPool()
    pws = [PrefillWorker(params, cfg, pool, prefill_chunk=chunk,
                         page_pool=pp) for _ in range(n_workers)]
    dw = DecodeWorker(params, cfg, max_batch=max_batch, max_len=max_len,
                      substrate="paged", page_pool=pp)
    return pws, dw, pp, pool


def _req(rid, toks, max_new, **kw):
    return ServingRequest(req_id=rid, tokens=toks, max_new=max_new, **kw)


def _oracle(cfg, params, reqs, max_news):
    """Request-at-a-time reference streams (never preempted)."""
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=64)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=1024)
    out = {}
    for rid, toks in reqs.items():
        res = pw(toks)
        dw.join(_req(rid, toks, max_news[rid]), res)
        seq = [res.first_token]
        while dw.n_active:
            for r, tok, fin in dw.step():
                seq.append(tok)
        out[rid] = seq
    return out


# ---------------------------------------------------------------------------
# export/import: the device→host demotion primitive
# ---------------------------------------------------------------------------

def test_export_run_roundtrip_transfers_ownership(setup):
    """export_run returns host copies and RELEASES the run (ownership
    transfer); import_run brings the bytes back page-exact. The exported
    arrays must not alias device pages that get recycled in between."""
    cfg, params = setup
    pp = DevicePagePool(cfg, n_pages=32, page_tokens=64)
    rng = np.random.default_rng(0)
    L = pp.k_pages.shape[0]
    (KV, Dh), _ = cfg.kv_widths             # by token; the slab is lane-dense
    n_tokens = 150                          # 3 pages, partial tail
    k = rng.standard_normal((L, n_tokens, KV, Dh)).astype(np.float32)
    v = rng.standard_normal((L, n_tokens, KV, Dh)).astype(np.float32)

    pages = pp.alloc(pp.pages_for(n_tokens))
    pp.write_run(pages, k, v)
    # reference in the pool's own KV dtype (write_run may downcast)
    k_ref, v_ref = (np.asarray(a).copy()
                    for a in pp.read_seq(pages, n_tokens))
    held_before = pp.used_pages
    ek, ev = pp.export_run(pages, n_tokens)
    assert pp.used_pages == held_before - len(pages)   # released
    assert pp.counters["pages_exported"] == len(pages)

    # clobber the freed pages: the export must have deep-copied
    junk = pp.alloc(pp.pages_for(n_tokens))
    pp.write_run(junk, np.zeros_like(k), np.zeros_like(v))
    np.testing.assert_array_equal(np.asarray(ek), k_ref)
    np.testing.assert_array_equal(np.asarray(ev), v_ref)

    back = pp.import_run(ek, ev, n_tokens)
    rk, rv = pp.read_seq(back, n_tokens)
    np.testing.assert_array_equal(np.asarray(rk), k_ref)
    np.testing.assert_array_equal(np.asarray(rv), v_ref)
    assert pp.counters["pages_imported"] == len(back)
    pp.release(junk)
    pp.release(back)
    pp.check_leaks()


def test_decode_worker_preempt_and_resume_bit_exact(setup):
    """preempt() mid-decode + join(resume_emitted=...) from the spilled
    bytes must continue the stream bit-exactly, and the slot's completion
    bound (reserved_growth_pages) must not drift across the cycle."""
    cfg, params = setup
    pws, dw, pp, _ = _mk(cfg, params, max_batch=2)
    pw = pws[0]
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, 200)
    max_new = 8

    res = pw(toks)
    slot = dw.join(_req(0, toks, max_new), res)
    for _ in range(3):
        dw.step()
    reserved_before = dw.reserved_growth_pages()
    run = dw.preempt(slot)
    assert dw.n_active == 0 and dw.stats()["preemptions"] == 1
    assert run.n_tokens == 200 + len(run.emitted) - 1  # pending input unwritten

    # restore through the stage path at the spilled depth
    from repro.serving.engine import stage_run
    ids = pw.hasher.hash_ids(np.concatenate(
        [toks, np.asarray(run.emitted[:-1], toks.dtype)]))
    pages = stage_run(pp, ids, run.k, run.v, run.n_tokens)
    assert pages is not None
    from repro.serving.engine import PrefillResult
    pres = PrefillResult(first_token=run.emitted[-1], kv_k=run.k,
                         kv_v=run.v, prompt_len=run.n_tokens,
                         reused_blocks=0, new_blocks=0, hash_ids=ids,
                         pages=pages, page_pool=pp, page_gens=pp.gens_of(pages))
    dw.join(run.request, pres, resume_emitted=run.emitted)
    assert dw.reserved_growth_pages() == reserved_before  # bound invariant
    assert dw.stats()["resumed_joins"] == 1
    emitted = list(run.emitted)
    while dw.n_active:
        for _, tok, _ in dw.step():
            emitted.append(tok)

    oracle = _oracle(cfg, params, {0: toks}, {0: max_new})
    assert emitted == oracle[0]
    pp.check_leaks()


def test_preempt_dense_substrate_rejected(setup):
    cfg, params = setup
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=256,
                      substrate="dense")
    with pytest.raises(RuntimeError, match="paged substrate"):
        dw.preempt(0)


def test_plan_restore_pricing():
    # forced modes win regardless of estimates
    assert plan_restore(512, reload_s_per_block=9.0,
                        recompute_s_per_block=1.0, mode="reload").mode \
        == "reload"
    # auto: cheaper measured arm wins; reload takes ties and unwarmed cases
    assert plan_restore(1024, reload_s_per_block=1.0,
                        recompute_s_per_block=2.0).mode == "reload"
    assert plan_restore(1024, reload_s_per_block=2.0,
                        recompute_s_per_block=1.0).mode == "recompute"
    assert plan_restore(1024, reload_s_per_block=1.0,
                        recompute_s_per_block=1.0).mode == "reload"
    assert plan_restore(1024, reload_s_per_block=None,
                        recompute_s_per_block=None).mode == "reload"
    p = plan_restore(1024, reload_s_per_block=None,
                     recompute_s_per_block=0.5)
    assert p.mode == "recompute" and p.est_recompute_s == pytest.approx(1.0)
    with pytest.raises(ValueError, match="unknown restore mode"):
        plan_restore(512, reload_s_per_block=1.0,
                     recompute_s_per_block=1.0, mode="warp")


def test_spill_slab_lifecycle():
    pool = HostKVPool()
    k = np.zeros((2, 8, 1, 4), np.float32)
    pool.spill_put(7, k, k, 8)
    assert pool.spill_depth() == 1
    with pytest.raises(RuntimeError, match="already has a spilled run"):
        pool.spill_put(7, k, k, 8)
    _, _, n = pool.spill_get(7)
    assert n == 8
    assert pool.spill_pop(7) and not pool.spill_pop(7)
    st = pool.stats()
    assert st["spills"] == 1 and st["spill_restores"] == 1
    assert st["spill_entries"] == 0 and st["spill_bytes"] == 0
    pool.close()


# ---------------------------------------------------------------------------
# the loop: preemption under mixed-priority contention
# ---------------------------------------------------------------------------

def _drive(loop):
    loop.close_intake()
    return loop.run()


def test_loop_preempts_low_priority_victim_bit_exact(setup):
    """Tight pool + full batch: a high-priority arrival that can never
    become obtainable by waiting must spill the low-priority victim,
    finish, and the victim must restore and complete — every stream
    bit-exact vs the never-preempted oracle."""
    cfg, params = setup
    for restore_mode in ("reload", "recompute", "auto"):
        pws, dw, pp, pool = _mk(cfg, params, max_batch=1, max_len=640,
                                n_pages=17)
        loop = ServingLoop(pws, dw, chunks_per_iter=2, max_queue=16,
                           restore_mode=restore_mode)
        rng = np.random.default_rng(10)
        victim_toks = rng.integers(0, cfg.vocab_size, 512)
        sprinter_toks = rng.integers(0, cfg.vocab_size, 128)
        max_news = {0: 24, 1: 4}
        assert loop.submit(_req(0, victim_toks, 24, priority=0))
        # let the victim join and decode a bit
        while len(loop.outputs.get(0, RequestOutput(0)).tokens) < 4:
            loop.iterate()
        assert loop.submit(_req(1, sprinter_toks, 4, priority=1))
        stats = _drive(loop)

        assert stats["completed"] == 2, restore_mode
        assert stats["preemptions"] >= 1, restore_mode
        out0 = loop.outputs[0]
        assert out0.preemptions >= 1 and len(out0.restores) >= 1
        if restore_mode != "auto":
            assert set(out0.restores) == {restore_mode}
        assert loop.outputs[1].preemptions == 0     # priority held
        oracle = _oracle(cfg, params,
                         {0: victim_toks, 1: sprinter_toks}, max_news)
        for rid in (0, 1):
            assert loop.outputs[rid].tokens == oracle[rid], \
                f"req {rid} diverged under restore_mode={restore_mode}"
        assert pool.spill_depth() == 0              # slab drained
        pp.check_leaks()
        assert stats["spill_depth"] == 0


def test_loop_preempt_disabled_and_equal_priority_defer(setup):
    """preempt=False — and equal priority classes even with it on — must
    degrade to the PR-6 defer-only behaviour: no preemptions, everything
    still completes."""
    cfg, params = setup
    for preempt, prio in ((False, 1), (True, 0)):
        pws, dw, pp, pool = _mk(cfg, params, max_batch=1, max_len=640,
                                n_pages=17)
        loop = ServingLoop(pws, dw, chunks_per_iter=2, max_queue=16,
                           preempt=preempt)
        rng = np.random.default_rng(11)
        assert loop.submit(_req(0, rng.integers(0, cfg.vocab_size, 384), 6))
        assert loop.submit(_req(1, rng.integers(0, cfg.vocab_size, 128), 3,
                                priority=prio))
        stats = _drive(loop)
        assert stats["completed"] == 2
        assert stats["preemptions"] == 0
        assert pool.spill_depth() == 0
        pp.check_leaks()


def test_loop_priority_orders_pending_joins(setup):
    """With one slot and several finished prefills pending, the higher
    priority class must join (and finish) first, FIFO within a class."""
    cfg, params = setup
    pws, dw, pp, _ = _mk(cfg, params, max_batch=1, max_len=512,
                         n_workers=2)
    loop = ServingLoop(pws, dw, chunks_per_iter=4, max_queue=16,
                       preempt=False)
    rng = np.random.default_rng(12)
    # a long blocker holds the single slot so every contender's prefill
    # finishes while it decodes — the pending-join queue then really has
    # all four at once and must drain in priority order
    assert loop.submit(_req(99, rng.integers(0, cfg.vocab_size, 64), 24,
                            priority=9))
    while dw.n_active == 0:
        loop.iterate()
    prios = {0: 3, 1: 2, 2: 1, 3: 2}
    for i, p in prios.items():
        assert loop.submit(_req(i, rng.integers(0, cfg.vocab_size, 96), 2,
                                priority=p))
    while len(loop._pending_join) < 4:
        loop.iterate()
        assert dw.n_active == 1          # blocker still pinning the slot
    stats = _drive(loop)
    assert stats["completed"] == 5
    order = [r for r in sorted(loop.outputs,
                               key=lambda r: loop.outputs[r].completed_iter)
             if r != 99]
    # non-increasing priority along the completion order
    ps = [prios[r] for r in order]
    assert ps == sorted(ps, reverse=True), (order, ps)
    assert [r for r in order if prios[r] == 2] == [1, 3]   # FIFO in class
    pp.check_leaks()


def test_loop_stop_mid_spill_releases_everything(setup):
    """stop() while a victim sits in the spill slab: no stranded slab
    entries, no leaked device pages, no stranded staged runs."""
    cfg, params = setup
    pws, dw, pp, pool = _mk(cfg, params, max_batch=1, max_len=640,
                            n_pages=17)
    loop = ServingLoop(pws, dw, chunks_per_iter=2, max_queue=16)
    rng = np.random.default_rng(13)
    assert loop.submit(_req(0, rng.integers(0, cfg.vocab_size, 512), 24))
    while len(loop.outputs.get(0, RequestOutput(0)).tokens) < 4:
        loop.iterate()
    assert loop.submit(_req(1, rng.integers(0, cfg.vocab_size, 128), 64,
                            priority=1))
    # drive until the spill happened but the victim has NOT restored
    # (the sprinter's 64 new tokens keep the slot busy a long time)
    while loop.stats()["preemptions"] == 0:
        loop.iterate()
    assert pool.spill_depth() == 1
    loop.stop()
    loop.run()
    assert dw.n_active == 0
    assert pool.spill_depth() == 0               # slab purged
    assert pool.stats()["spill_drops"] == 1      # abandoned, not restored
    pp.check_leaks()
    pool.close()


def test_loop_stop_mid_restore_releases_everything(setup):
    """stop() after the victim re-entered the pending-join path (restore
    staged or rerouted through recompute prefill) must still unwind."""
    cfg, params = setup
    pws, dw, pp, pool = _mk(cfg, params, max_batch=1, max_len=640,
                            n_pages=17)
    loop = ServingLoop(pws, dw, chunks_per_iter=1, max_queue=16,
                       restore_mode="recompute")
    rng = np.random.default_rng(14)
    assert loop.submit(_req(0, rng.integers(0, cfg.vocab_size, 512), 24))
    while len(loop.outputs.get(0, RequestOutput(0)).tokens) < 4:
        loop.iterate()
    assert loop.submit(_req(1, rng.integers(0, cfg.vocab_size, 128), 4,
                            priority=1))
    # run until the victim's recompute prefill is mid-chunks
    while loop.stats()["restores_recompute"] == 0:
        loop.iterate()
    loop.stop()
    loop.run()
    assert dw.n_active == 0
    assert pool.spill_depth() == 0
    pp.check_leaks()
    pool.close()


# ---------------------------------------------------------------------------
# unified request API + deprecation shims
# ---------------------------------------------------------------------------

def test_serving_request_validation():
    with pytest.raises(ValueError, match="max_new"):
        ServingRequest(req_id=0, tokens=np.arange(4), max_new=0)
    r = ServingRequest(req_id=1, tokens=[1, 2, 3], max_new=2)
    assert isinstance(r.tokens, np.ndarray)          # coerced


def test_submit_legacy_kwargs_deprecated(setup):
    cfg, params = setup
    pws, dw, pp, _ = _mk(cfg, params)
    loop = ServingLoop(pws, dw, max_queue=8)
    rng = np.random.default_rng(15)
    toks = rng.integers(0, cfg.vocab_size, 80)
    with pytest.warns(DeprecationWarning, match="pass a ServingRequest"):
        assert loop.submit(0, toks, max_new=2)
    stats = _drive(loop)
    assert stats["completed"] == 1
    assert loop.outputs[0].done and len(loop.outputs[0].tokens) == 2
    pp.check_leaks()


def test_join_legacy_positional_deprecated(setup):
    cfg, params = setup
    pws, dw, pp, _ = _mk(cfg, params)
    rng = np.random.default_rng(16)
    toks = rng.integers(0, cfg.vocab_size, 80)
    res = pws[0](toks)
    with pytest.warns(DeprecationWarning, match="pass a ServingRequest"):
        dw.join(0, res, max_new=2)
    while dw.n_active:
        dw.step()
    # conflicting explicit max_new must be rejected, not silently ignored
    res2 = pws[0](toks)
    with pytest.raises(ValueError, match="conflicts with request.max_new"):
        dw.join(_req(1, toks, 3), res2, max_new=4)
    res2.release_pages()
    pp.check_leaks()


def test_submit_requires_tokens(setup):
    cfg, params = setup
    pws, dw, _, _ = _mk(cfg, params)
    loop = ServingLoop(pws, dw)
    with pytest.raises(ValueError, match="tokens is required"):
        loop.submit(ServingRequest(req_id=0, tokens=None, max_new=2))


# ---------------------------------------------------------------------------
# submit() queue-cap TOCTOU
# ---------------------------------------------------------------------------

def test_submit_queue_cap_atomic_under_contention(setup):
    """The old submit read qsize() then put() without holding the lock:
    N racing submitters could all pass the cap check and overfill the
    queue. The check+enqueue are now one atomic step."""
    cfg, params = setup
    pws, dw, _, _ = _mk(cfg, params)

    class RacyLoop(ServingLoop):
        """Widen the race window: every qsize() read yields the GIL, so
        the pre-fix interleave (all threads read a below-cap size, then
        all put) is effectively guaranteed."""
        def signal(self):
            import time as _t
            sig = super().signal()
            _t.sleep(0.002)
            return sig

    cap = 4
    loop = RacyLoop(pws, dw, max_queue=cap, admission="baseline")
    rng = np.random.default_rng(17)
    toks = rng.integers(0, cfg.vocab_size, 64)
    n_threads = 16
    barrier = threading.Barrier(n_threads)
    results = [None] * n_threads

    def submitter(i):
        barrier.wait()
        results[i] = loop.submit(_req(i, toks, 1))

    threads = [threading.Thread(target=submitter, args=(i,),
                                name=f"repro-submit-{i}")
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    accepted = sum(bool(r) for r in results)
    assert loop._arrivals.qsize() == accepted
    assert accepted <= cap, \
        f"{accepted} submits raced past the max_queue={cap} cap"
    st = loop.stats()
    assert st["submitted"] == n_threads
    assert st["rejected"] == n_threads - accepted
    loop.stop()
    loop.run()
