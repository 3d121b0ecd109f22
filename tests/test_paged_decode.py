"""Paged decode substrate: kernel-vs-oracle parity, page refcount/COW
properties, zero-copy prefill→decode handoff, incremental prefix hashing,
and chunk-skipping overlap assembly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # sandboxed env: vendored shim (seeded random)
    from _hypothesis_shim import given, settings, strategies as st

from repro.configs.base import get_config
from repro.core.trace import BLOCK_TOKENS
from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_attention_layers)
from repro.kernels.paged_attention.ref import (paged_attention_layers_ref,
                                               paged_attention_ref)
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  PrefixHasher, prefix_hash_ids)
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest

CFG = get_config("smollm-360m").reduced()
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def setup():
    params = __import__("repro.models.transformer",
                        fromlist=["init_params"]).init_params(CFG, KEY)
    return CFG, params


# ---------------------------------------------------------------- kernel ----

def _rand_paged(B, H, KV, D, P, page, mp, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, page, KV, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P, page, KV, D), jnp.bfloat16)
    return q, kp, vp


@pytest.mark.parametrize("lens", [
    [1, 64, 65, 128],          # ragged incl. exact page boundaries
    [63, 64, 127, 256],        # page-1 / page / page·2-1 / max
    [10, 10, 10, 10],
])
def test_kernel_oracle_parity_ragged_and_boundary(lens):
    B, H, KV, D, P, page, mp = 4, 8, 2, 64, 32, 64, 4
    q, kp, vp = _rand_paged(B, H, KV, D, P, page, mp)
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.integers(1, P, (B, mp)), jnp.int32)
    out = paged_attention(q, kp, vp, table,
                          jnp.asarray(lens, jnp.int32), interpret=True)
    ref = paged_attention_ref(q, kp, vp, table,
                              jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_kernel_oracle_parity_null_page():
    """Rows padded with the null page (id 0) beyond their used span must
    agree — the masked tail never contributes, whatever page 0 holds."""
    B, H, KV, D, P, page, mp = 2, 4, 4, 64, 16, 64, 4
    q, kp, vp = _rand_paged(B, H, KV, D, P, page, mp)
    kp = kp.at[0].set(1e4)     # poison the null page
    vp = vp.at[0].set(-1e4)
    table = jnp.asarray([[3, 0, 0, 0], [5, 7, 0, 0]], jnp.int32)
    lens = jnp.asarray([40, 100], jnp.int32)
    out = paged_attention(q, kp, vp, table, lens, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_batched_over_layers_entry_matches_per_layer():
    L, B, H, KV, D, P, page, mp = 3, 2, 8, 2, 64, 16, 64, 2
    ks = jax.random.split(KEY, 3)
    qs = jax.random.normal(ks[0], (L, B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (L, P, page, KV, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (L, P, page, KV, D), jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(1, P, (B, mp)), jnp.int32)
    lens = jnp.asarray([70, 128], jnp.int32)
    out = paged_attention_layers(qs, kp, vp, table, lens, interpret=True)
    per_layer = jnp.stack([
        paged_attention(qs[l], kp[l], vp[l], table, lens, interpret=True)
        for l in range(L)])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(per_layer, np.float32),
                               atol=2e-2, rtol=2e-2)
    ref = paged_attention_layers_ref(qs, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("entry", ["per_layer", "layers"])
@pytest.mark.parametrize("case", ["qh2kv", "window", "indivisible"])
def test_pallas_refuses_what_it_cannot_take(entry, case):
    """use_pallas=True on a padded head map, a window, or heads that do
    not divide raises; it never quietly returns the oracle instead."""
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_decode_attention_layers)
    B, H, KV, D, P, page, mp = 2, 6 if case == "indivisible" else 4, 4, \
        32, 8, 16, 2
    q, kp, vp = _rand_paged(B, H, KV, D, P, page, mp)
    kw = dict(use_pallas=True, interpret=True)
    if case == "qh2kv":
        kw["qh2kv"] = jnp.asarray([0, 1, 2, 3], jnp.int32)
    elif case == "window":
        kw["window"] = 8
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lens = jnp.asarray([20, 9], jnp.int32)
    with pytest.raises(ValueError, match="grouped GQA"):
        if entry == "per_layer":
            paged_decode_attention(q, kp, vp, table, lens, **kw)
        else:
            paged_decode_attention_layers(q[None], kp[None], vp[None],
                                          table, lens, **kw)


def test_ref_qh2kv_matches_manual_expansion():
    """The padded-GQA oracle (explicit query→kv map) equals attention over
    manually expanded pages."""
    B, H, KV, D, P, page, mp = 2, 6, 2, 32, 8, 16, 2
    q, kp, vp = _rand_paged(B, H, KV, D, P, page, mp, seed=5)
    qh2kv = jnp.asarray([0, 0, 0, 1, 1, 0], jnp.int32)  # padded head -> kv 0
    table = jnp.asarray([[2, 3], [4, 0]], jnp.int32)
    lens = jnp.asarray([20, 9], jnp.int32)
    out = paged_attention_ref(q, kp, vp, table, lens, qh2kv=qh2kv)
    kp_x = jnp.take(kp, qh2kv, axis=2)     # (P, page, H, D)
    vp_x = jnp.take(vp, qh2kv, axis=2)
    ref = paged_attention_ref(q, kp_x, vp_x, table, lens)  # grouped H==KV
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------ page pool ----

def _tiny_pool(n_pages=24, page_tokens=64):
    return DevicePagePool(CFG, n_pages=n_pages, page_tokens=page_tokens)


def _rand_kv(n_tokens, seed=0):
    rng = np.random.default_rng(seed)
    L, KV, Dh = CFG.attention_layers, CFG.n_kv_heads, CFG.head_dim
    k = rng.standard_normal((L, n_tokens, KV, Dh)).astype(np.float32)
    return k, -k


def test_page_tokens_must_divide_block():
    with pytest.raises(ValueError):
        DevicePagePool(CFG, n_pages=8, page_tokens=96)


def test_double_free_raises():
    pool = _tiny_pool()
    run = pool.alloc(2)
    pool.release(run)
    with pytest.raises(RuntimeError):
        pool.release(run)
    pool.check_leaks()


def test_registry_adopt_shares_physical_pages():
    pool = _tiny_pool(n_pages=24)
    k, v = _rand_kv(BLOCK_TOKENS)
    run = pool.alloc(pool.pages_per_block)
    pool.write_run(run, k, v)
    pool.register_block(1234, run)
    n_free0 = len(pool.free)
    n, pages = pool.adopt_chain([1234, 999])
    assert n == 1 and pages == run
    assert len(pool.free) == n_free0        # no new pages for the adopter
    pool.release(pages)
    pool.release(run)                        # the staging reference
    pool.check_leaks()
    # registry still holds the run; eviction under pressure frees it
    pool.alloc(len(pool.free) + pool.pages_per_block)
    assert 1234 not in pool.runs
    assert pool.stats()["registry_evictions"] == 1


def test_registry_eviction_pins_live_runs():
    pool = _tiny_pool(n_pages=1 + 2 * 8)
    k, v = _rand_kv(BLOCK_TOKENS)
    run = pool.alloc(pool.pages_per_block)
    pool.write_run(run, k, v)
    pool.register_block(7, run)             # run refs: staging + registry
    with pytest.raises(MemoryError):        # live ref pins the run
        pool.alloc(2 * pool.pages_per_block)
    pool.release(run)                       # drop staging ref -> evictable
    pool.alloc(2 * pool.pages_per_block)    # now eviction makes room
    assert 7 not in pool.runs


def test_cow_never_mutates_shared_page():
    pool = _tiny_pool()
    k, v = _rand_kv(64, seed=3)
    run = pool.alloc(1)
    pool.write_run(run, k, v)
    pool.retain(run)                        # second owner -> shared
    before = np.asarray(pool.k_pages[:, run[0]]).copy()
    new = pool.make_writable(run[0])
    assert new != run[0]
    pool.k_pages = pool.k_pages.at[:, new, 0].set(99.0)  # append-style write
    np.testing.assert_array_equal(np.asarray(pool.k_pages[:, run[0]]), before)
    np.testing.assert_array_equal(                      # copy carried bytes
        np.asarray(pool.k_pages[:, new, 1:]), before[:, 1:])
    assert pool.refs[run[0]] == 1 and pool.refs[new] == 1
    pool.release([new])
    pool.release(run)
    pool.check_leaks()


def test_exclusive_page_skips_cow():
    pool = _tiny_pool()
    run = pool.alloc(1)
    assert pool.make_writable(run[0]) == run[0]
    assert pool.stats()["cow_copies"] == 0
    pool.release(run)


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 6)),
                min_size=1, max_size=20))
@settings(max_examples=30, deadline=None)
def test_alloc_retain_release_conserve_pages(ops):
    """Random alloc/retain/release cycles: every page is either free or
    referenced, never both, never leaked (op 0 alloc, 1 retain, 2 release)."""
    pool = _tiny_pool(n_pages=16)
    held: list[list[int]] = []
    for op, n in ops:
        if op == 0:
            try:
                held.append(pool.alloc(n))
            except MemoryError:
                pass
        elif op == 1 and held:
            run = held[n % len(held)]
            pool.retain(run)
            held.append(list(run))
        elif op == 2 and held:
            pool.release(held.pop(n % len(held)))
        pool.check_leaks()
        n_held = sum(len(r) for r in held)
        assert int(pool.refs.sum()) == n_held
    for run in held:
        pool.release(run)
    pool.check_leaks()
    assert len(pool.free) == pool.n_pages - 1


# ------------------------------------------------- engine: paged decode ----

def test_paged_matches_dense_with_prefix_sharing(setup):
    """Continuous batching over the paged substrate — zero-copy joins,
    shared prefix pages — emits exactly the dense arena's tokens."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, 1024)
    reqs = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)])
            for n in (96, 64, 200)]

    pool = HostKVPool()
    pp = DevicePagePool(cfg, n_pages=1 + 5 * 32, page_tokens=64)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=4, max_len=2048,
                      substrate="paged", page_pool=pp)
    pool_d = HostKVPool()
    pw_d = PrefillWorker(params, cfg, pool_d, prefill_chunk=256)
    dw_d = DecodeWorker(params, cfg, max_batch=4, max_len=2048,
                        substrate="dense")

    outs, outs_d = {}, {}
    for i, t in enumerate(reqs):
        r = pw(t)
        assert r.pages is not None
        dw.join(i, r, max_new=6)
        outs[i] = [r.first_token]
        rd = pw_d(t)
        dw_d.join(i, rd, max_new=6)
        outs_d[i] = [rd.first_token]
    while dw.n_active or dw_d.n_active:
        for rid, tok, _ in dw.step():
            outs[rid].append(tok)
        for rid, tok, _ in dw_d.step():
            outs_d[rid].append(tok)
    assert outs == outs_d
    assert dw.stats()["zero_copy_joins"] == 3      # adoption, no dense copy
    assert pp.stats()["shared_adoptions"] >= 2     # reqs 2,3 shared 2 blocks
    pp.check_leaks()


@pytest.mark.parametrize("heads", ["grouped", "qh2kv"])
def test_carried_step_matches_dense(heads):
    """The paged step, its lane-dense slabs carried whole through the
    layer scan and each layer's rows written and gathered by index,
    emits the dense arena's tokens bit for bit: grouped GQA (16 query
    heads over 2 KV heads), and the explicit query→KV head map of 4
    query heads padded to 16."""
    import dataclasses
    from repro.models.layers import GROUPED_ATTN
    from repro.models.transformer import init_params
    cfg = CFG if heads == "qh2kv" else dataclasses.replace(CFG, n_heads=16)
    grouped = GROUPED_ATTN and cfg.padded_heads == cfg.n_heads
    assert grouped == (heads == "grouped")
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(6)
    reqs = [rng.integers(0, cfg.vocab_size, n) for n in (70, 600, 130)]
    L, KV, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    outs = {}
    for substrate in ("paged", "dense"):
        pw = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=256)
        dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                          substrate=substrate)
        if substrate == "paged":
            assert dw.page_pool.k_pages.shape[::2] == (L, 64)
            assert dw.page_pool.k_pages.shape[3] == KV * Dh
        got = {}
        for i, t in enumerate(reqs):
            r = pw(t)
            dw.join(ServingRequest(req_id=i, tokens=t, max_new=5), r)
            got[i] = [r.first_token]
        while dw.n_active:
            for rid, tok, _ in dw.step():
                got[rid].append(tok)
        outs[substrate] = got
    assert outs["paged"] == outs["dense"]


def test_lane_dense_pool_round_trips_bit_exact():
    """The pool keeps (L, P, page, KV·Dh) slabs and takes and gives K and
    V by token: ``write_run`` then ``read_seq``, from host arrays and from
    lane-dense device rows, and ``export_run`` then ``import_run`` give
    back the written bytes (in the pool's dtype) exactly."""
    pool = _tiny_pool(n_pages=24)
    L, KV, Dh = CFG.attention_layers, CFG.n_kv_heads, CFG.head_dim
    assert pool.k_pages.shape == (L, 24, 64, KV * Dh)
    n = 150                                  # 3 pages, a partial tail
    k, v = _rand_kv(n, seed=8)
    want = [np.asarray(jnp.asarray(x, pool.k_pages.dtype)) for x in (k, v)]
    run = pool.alloc(pool.pages_for(n))
    pool.write_run(run, k, v)
    got = pool.read_seq(run, n)
    for g, w in zip(got, want):
        assert g.shape == (L, n, KV, Dh)
        np.testing.assert_array_equal(g, w)
    rows = pool.alloc(pool.pages_for(n))
    pool.write_run(rows, *(jnp.asarray(x).reshape(L, n, KV * Dh)
                           for x in want))
    for g, w in zip(pool.read_seq(rows, n), want):
        np.testing.assert_array_equal(g, w)
    back = pool.import_run(*pool.export_run(run, n), n)
    for g, w in zip(pool.read_seq(back, n), want):
        np.testing.assert_array_equal(g, w)
    pool.release(rows)
    pool.release(back)
    pool.check_leaks()


def test_slots_leaving_release_pages(setup):
    cfg, params = setup
    rng = np.random.default_rng(4)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=128)
    dw = DecodeWorker(params, cfg, max_batch=2, max_len=512,
                      substrate="paged")
    pp = dw.page_pool
    for i in range(3):                      # more requests than slots
        r = pw(rng.integers(0, cfg.vocab_size, 80 + 40 * i))
        dw.join(i, r, max_new=2)
        while dw.n_active == dw.max_batch:
            dw.step()
    while dw.n_active:
        dw.step()
    pp.check_leaks()
    # only registry-held runs may remain; they are evictable
    for h, run in pp.runs.items():
        assert all(pp.refs[p] == 1 for p in run)


def test_multi_join_cow_bit_exact(setup):
    """One PrefillResult joined into two slots (n-best fan-out): the slots
    share every page incl. the partial tail; the first append COWs and
    both decode exactly like the lone sequential oracle."""
    from repro.models.transformer import (decode_step, init_caches,
                                          init_params, prefill)
    cfg, params = setup
    rng = np.random.default_rng(5)
    t = rng.integers(0, cfg.vocab_size, 600)
    pool = HostKVPool()
    pp = DevicePagePool(cfg, n_pages=1 + 4 * 16, page_tokens=64)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=4, max_len=1024,
                      substrate="paged", page_pool=pp)
    r = pw(t)
    dw.join(0, r, max_new=5)
    dw.join(1, r, max_new=5)
    outs = {0: [r.first_token], 1: [r.first_token]}
    while dw.n_active:
        for rid, tok, _ in dw.step():
            outs[rid].append(tok)
    assert outs[0] == outs[1]
    assert dw.stats()["zero_copy_joins"] == 2
    assert pp.stats()["cow_copies"] >= 1
    pp.check_leaks()

    logits, caches = jax.jit(lambda p, t_: prefill(p, t_, cfg))(
        params, jnp.asarray(t[None]))
    full = init_caches(cfg, 1, 1024)
    S = len(t)
    full = full._replace(kv=full.kv._replace(
        k=full.kv.k.at[:, :, :S].set(caches.kv.k),
        v=full.kv.v.at[:, :, :S].set(caches.kv.v)), length=caches.length)
    tok = int(jnp.argmax(logits[0]))
    ref = [tok]
    step = jax.jit(lambda p, t_, c: decode_step(p, t_, c, cfg))
    for _ in range(4):
        lg, full = step(params, jnp.asarray([[tok]], jnp.int32), full)
        tok = int(jnp.argmax(lg[0, -1]))
        ref.append(tok)
    assert outs[0] == ref


def test_rejoin_after_release_raises_not_corrupts(setup):
    """Joining a PrefillResult AFTER its joined slot finished (staging
    reference long gone, tail pages recycled) must raise, never attend
    another request's recycled pages."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    pool = HostKVPool()
    pp = DevicePagePool(cfg, n_pages=1 + 4 * 8, page_tokens=64)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=128, page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=2, max_len=512,
                      substrate="paged", page_pool=pp)
    r1 = pw(rng.integers(0, cfg.vocab_size, 100))
    dw.join(0, r1, max_new=2)
    while dw.n_active:
        dw.step()                        # slot done -> r1's pages released
    r2 = pw(rng.integers(0, cfg.vocab_size, 100))   # recycles the pages
    dw.join(1, r2, max_new=2)
    with pytest.raises(RuntimeError):
        dw.join(0, r1, max_new=2)        # stale run must be refused
    pp.check_leaks()


def test_release_pages_for_never_joined_result(setup):
    cfg, params = setup
    rng = np.random.default_rng(12)
    pool = HostKVPool()
    pp = DevicePagePool(cfg, n_pages=1 + 2 * 8, page_tokens=64)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=128, page_pool=pp)
    r = pw(rng.integers(0, cfg.vocab_size, 100))
    held = pp.used_pages
    assert held > 0
    r.release_pages()                    # cancelled before any join
    r.release_pages()                    # idempotent
    pp.check_leaks()
    assert pp.used_pages < held


def test_join_rejects_prompt_that_would_outgrow_table(setup):
    cfg, params = setup
    rng = np.random.default_rng(13)
    pool = HostKVPool()
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=128)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=512,
                      substrate="paged")
    r = pw(rng.integers(0, cfg.vocab_size, 510))
    with pytest.raises(ValueError):      # 510 + 8 > 512
        dw.join(0, r, max_new=8)
    dw.join(0, r, max_new=2)             # 510 + 2 fits exactly
    while dw.n_active:
        dw.step()
    dw.page_pool.check_leaks()


# ------------------------------------------------- incremental hashing ----

def test_prefix_hasher_matches_reference():
    rng = np.random.default_rng(7)
    t = rng.integers(0, 50000, 1700)
    assert PrefixHasher().hash_ids(t) == prefix_hash_ids(t)


def test_prefix_hasher_session_hashes_only_suffix():
    rng = np.random.default_rng(8)
    turn1 = rng.integers(0, 50000, 1024)            # 2 blocks
    turn2 = np.concatenate([turn1, rng.integers(0, 50000, 1024)])  # +2
    h = PrefixHasher()
    ids1 = h.hash_ids(turn1, session="s")
    assert h.blocks_hashed == 2
    ids2 = h.hash_ids(turn2, session="s")
    assert h.blocks_hashed == 4                     # only the suffix hashed
    assert h.memo_hits == 1
    assert ids2[:2] == ids1
    assert ids2 == prefix_hash_ids(turn2)


def test_prefix_hasher_divergence_falls_back():
    rng = np.random.default_rng(9)
    a = rng.integers(0, 50000, 1024)
    b = a.copy()
    b[10] += 1                                      # diverge in block 0
    h = PrefixHasher()
    h.hash_ids(a, session="s")
    ids_b = h.hash_ids(b, session="s")
    assert h.memo_hits == 0
    assert ids_b == prefix_hash_ids(b)
    # memo replaced: a third call extending b resumes from b's chain
    c = np.concatenate([b, rng.integers(0, 50000, 512)])
    assert h.hash_ids(c, session="s") == prefix_hash_ids(c)
    assert h.memo_hits == 1


def test_prefix_hasher_memo_is_bounded():
    rng = np.random.default_rng(14)
    h = PrefixHasher(capacity_sessions=4)
    for s in range(10):
        h.hash_ids(rng.integers(0, 50000, 512), session=s)
    assert len(h._memo) == 4
    assert list(h._memo) == [6, 7, 8, 9]     # LRU: oldest sessions evicted


# --------------------------------------------- chunk-skipping assembly ----

def test_chunk_skipping_bit_exact_and_fewer_tokens(setup, tmp_path):
    """A fragmented chain (DRAM blocks interleaved past SSD ones inside
    the head span) assembles the DRAM blocks from the pool instead of
    recomputing them: bit-exact first token, strictly fewer computed
    tokens, skipped blocks counted."""
    from repro.models.transformer import prefill
    cfg, params = setup
    rng = np.random.default_rng(10)
    t = rng.integers(0, cfg.vocab_size, 6 * 512 + 100)

    pool = HostKVPool(capacity_blocks=2, ssd_capacity_blocks=16,
                      ssd_dir=str(tmp_path), writeback_batch=1)
    pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                       ssd_mode="overlap")
    r1 = pw(t)                          # cold: blocks 0-5 inserted; DRAM
    first_cold = r1.first_token         # holds the 2 most recent (4, 5)
    ids = prefix_hash_ids(t)
    pool.meta.touch_keys(ids[2:4])      # promote 2,3 -> 4,5 demote: the
    tiers = [pool.meta.resident_tier(h) for h in ids]  # chain fragments
    d0 = 0
    while d0 < len(tiers) and tiers[d0] == "dram":
        d0 += 1
    assert any(x == "dram" for x in tiers[d0:]), f"not fragmented: {tiers}"
    assert any(x == "ssd" for x in tiers[max(i for i, x in enumerate(tiers)
                                             if x == "dram"):]), tiers

    # expensive loads + ~free compute -> the split recomputes every SSD
    # block, chunk-skipping the DRAM blocks embedded in the span
    pool.store._read_s_ema = 10.0
    pw._t_block_ema = 1e-6
    computed0 = pw.stats()["computed_tokens"]
    r2 = pw(t)
    assert r2.first_token == first_cold
    logits, _ = jax.jit(lambda p, t_: prefill(p, t_, cfg))(
        params, jnp.asarray(t[None]))
    assert r2.first_token == int(jnp.argmax(logits[0]))

    assert r2.skipped_blocks >= 1       # DRAM blocks mid-span not recomputed
    computed = pw.stats()["computed_tokens"] - computed0
    # wholesale head recompute (the pre-chunk-skipping schedule) computes
    # every head-span block, skipped ones included
    wholesale = len(t) - (r2.reused_blocks - r2.skipped_blocks) * 512
    assert computed < wholesale         # strictly fewer than wholesale
    assert computed == len(t) - r2.reused_blocks * 512
    pool.close()


def test_overlap_split_prices_skipped_dram_free():
    from repro.serving.layerwise import overlap_split
    # ssd ssd dram dram ssd: with cheap compute the whole span recomputes
    # EXCEPT the dram blocks, which are skipped
    ov = overlap_split(["ssd", "ssd", "dram", "dram", "ssd"], 0.1, 10.0)
    assert ov.split == 5
    assert ov.head_recompute == 3
    assert ov.head_skipped == 2
    assert ov.t_head == pytest.approx(0.3)


# ------------------------------- prefill → decode handoff on the device ----

def _held_blocks(pool, ids):
    """The host pool's bytes of each block of ``ids`` it holds."""
    return {h: pool.get([h]) for h in ids if h in pool.meta}


def _fragment(pool, pw, t):
    """Make the next prefill of ``t`` take the overlapped path with
    recomputed head runs around chunk-skipped DRAM blocks (the recipe of
    ``test_chunk_skipping_bit_exact_and_fewer_tokens``)."""
    ids = prefix_hash_ids(t)
    pool.meta.touch_keys(ids[2:4])
    pool.store._read_s_ema = 10.0
    pw._t_block_ema = 1e-6


def _handoff_case(case, rng, vocab):
    """(history prompts, final prompt, needs the SSD tier) of a case."""
    a = rng.integers(0, vocab, 1100)
    return {
        "cold": ([], a[:1024], False),
        "tailed": ([], a[:700], False),
        "partial_hit": ([a], np.concatenate(
            [a[:BLOCK_TOKENS], rng.integers(0, vocab, 650)]), False),
        "full_hit": ([a[:1024]], a[:1024], False),
        "overlapped": (None, rng.integers(0, vocab, 6 * 512 + 100), True),
    }[case]


@pytest.mark.parametrize("case", ["cold", "tailed", "partial_hit",
                                  "full_hit", "overlapped"])
def test_device_handoff_matches_host_staging(setup, tmp_path, case):
    """A prefill that stages its pages from the device dense cache writes
    the same page run as staging from host bytes, leaves the host pool
    exactly as reading everything back did, and reads back only what the
    host pool stores."""
    from repro.serving.engine import stage_run
    cfg, params = setup
    history, final, ssd = _handoff_case(case, np.random.default_rng(11),
                                        cfg.vocab_size)
    out = {}
    for side in ("device", "host"):
        if ssd:
            pool = HostKVPool(capacity_blocks=2, ssd_capacity_blocks=16,
                              ssd_dir=str(tmp_path / side),
                              writeback_batch=1)
        else:
            pool = HostKVPool()
        pp = DevicePagePool(cfg, n_pages=1 + 4 * 32, page_tokens=64) \
            if side == "device" else None
        pw = PrefillWorker(params, cfg, pool, prefill_chunk=256,
                           ssd_mode="overlap", page_pool=pp)
        if ssd:
            pw(final)
            _fragment(pool, pw, final)
        else:
            for t in history:
                pw(t).release_pages()
        before = pw.stats()
        res = pw(final)
        delta = {k: v - before[k] for k, v in pw.stats().items()}
        out[side] = (pool, pp, res, delta)
    (pool_d, pp, rd, dd), (pool_h, _, rh, dh) = out["device"], out["host"]
    S = len(final)
    assert rd.overlapped == rh.overlapped == ssd
    assert rd.skipped_blocks == rh.skipped_blocks == (2 if ssd else 0)
    assert rd.first_token == rh.first_token
    assert (dd["device_stages"], dd["host_stages"]) == (1, 0)
    assert (dh["device_stages"], dh["host_stages"]) == (0, 1)
    # only the stored blocks came back; the host side read the whole cache
    assert dd["d2h_bytes"] == dd["put_bytes"] == dh["put_bytes"]
    assert dh["d2h_bytes"] == \
        dh["put_bytes"] + rh.kv_k.nbytes + rh.kv_v.nbytes
    if case == "full_hit":
        assert dd["put_bytes"] > 0          # the recomputed last block
    # the page run is the one host bytes stage
    assert rd.kv_k is None and rd.pages is not None
    ref = DevicePagePool(cfg, n_pages=1 + 4 * 32, page_tokens=64)
    ref_pages = stage_run(ref, rh.hash_ids, rh.kv_k, rh.kv_v, S)
    for got, want in zip(pp.read_seq(rd.pages, S),
                         ref.read_seq(ref_pages, S)):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(rd.host_kv(), rh.host_kv()):
        np.testing.assert_array_equal(got, want)
    # the host pool holds the same blocks, byte for byte
    ids = rh.hash_ids
    held_d, held_h = _held_blocks(pool_d, ids), _held_blocks(pool_h, ids)
    assert sorted(held_d) == sorted(held_h) and held_d
    for h in held_h:
        for got, want in zip(held_d[h], held_h[h]):
            np.testing.assert_array_equal(got, want)
    rd.release_pages()
    pool_d.close()
    pool_h.close()


def test_page_oom_falls_back_to_host_bytes(setup):
    """A page pool too small for the run: the prefill reads its whole
    cache back too, counted as a host stage, and ``host_kv`` gives those
    bytes."""
    cfg, params = setup
    t = np.random.default_rng(12).integers(0, cfg.vocab_size, 700)
    pp = DevicePagePool(cfg, n_pages=4, page_tokens=64)
    pw = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=256,
                       page_pool=pp)
    r = pw(t)
    assert r.pages is None and r.kv_k.shape[1] == len(t)
    st = pw.stats()
    assert (st["device_stages"], st["host_stages"]) == (0, 1)
    assert st["d2h_bytes"] == st["put_bytes"] + r.kv_k.nbytes + r.kv_v.nbytes
    assert st["put_bytes"] > 0              # its one block, read first
    assert r.host_kv()[0] is r.kv_k
    pp.check_leaks()


def test_host_kv_of_a_recycled_run_raises(setup):
    """``host_kv`` of a device-staged result reads its pages while they
    hold what was staged, and refuses once they were freed and re-used."""
    from repro.serving.request import ServingRequest
    cfg, params = setup
    t = np.random.default_rng(13).integers(0, cfg.vocab_size, 600)
    pp = DevicePagePool(cfg, n_pages=1 + 3 * 16, page_tokens=64)
    pw = PrefillWorker(params, cfg, HostKVPool(), prefill_chunk=256,
                       page_pool=pp)
    dw = DecodeWorker(params, cfg, max_batch=1, max_len=1024,
                      substrate="paged", page_pool=pp)
    r = pw(t)
    k, _ = r.host_kv()
    assert k.shape[1] == len(t)
    dw.join(ServingRequest(req_id=0, tokens=t, max_new=2), r)
    while dw.n_active:
        dw.step()
    pp.alloc(pp.free_pages)                 # recycle every freed page
    with pytest.raises(RuntimeError, match="re-used"):
        r.host_kv()


@pytest.mark.parametrize("per_page", [True, False])
def test_page_write_donates_both_slabs(per_page):
    """The compiled page write aliases both slabs to its outputs (no
    slab-sized copy), and either form writes the same bytes."""
    from repro.serving.paged_cache import page_write
    pool = _tiny_pool(n_pages=24)
    k, v = _rand_kv(3 * 64 + 10, seed=4)
    idx = np.asarray([5, 2, 17, 9], np.int32)
    write = page_write(64, per_page=per_page)
    text = write.lower(pool.k_pages, pool.v_pages, idx, k, v).compile() \
        .as_text()
    assert "input_output_alias={ {0}: (0, {}, may-alias), " \
        "{1}: (1, {}, may-alias) }" in text
    kp, vp = write(pool.k_pages, pool.v_pages, idx, k, v)
    assert pool.k_pages.is_deleted()        # donated
    for got, want in ((kp, k), (vp, v)):
        got = np.asarray(got, np.float32)[:, idx].reshape(
            k.shape[0], -1, *k.shape[2:])
        want = np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(got[:, :want.shape[1]], want)
        assert not got[:, want.shape[1]:].any()   # zero-padded tail page


def test_staging_at_other_offsets_adds_no_compile():
    """Blocks staged from a device dense cache compile one program per
    (prompt length, block shape): the same shapes at other offsets, or
    with other page ids, reuse them."""
    from repro.serving.engine import _device_tokens, stage_run
    L, KV, Dh = CFG.attention_layers, CFG.n_kv_heads, CFG.head_dim
    S = 3 * BLOCK_TOKENS + 100
    pool = _tiny_pool(n_pages=1 + 2 * 28)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    dense = [jax.random.normal(kk, (L, 1, S, KV, Dh), jnp.bfloat16)
             for kk in keys]
    pages = stage_run(pool, [1, 2, 3], dense[0], dense[1], S)
    n_write, n_take = pool._write._cache_size(), _device_tokens._cache_size()
    assert n_write == 2                     # a full block, the tail
    k, v = pool.read_seq(pages, S)
    np.testing.assert_array_equal(k, np.asarray(dense[0][:, 0]))
    np.testing.assert_array_equal(v, np.asarray(dense[1][:, 0]))
    again = stage_run(pool, [4, 5, 6], dense[2], dense[3], S)
    for lo in (0, 100, BLOCK_TOKENS, 2 * BLOCK_TOKENS):
        _device_tokens(dense[2], lo, BLOCK_TOKENS)
    assert pool._write._cache_size() == n_write
    assert _device_tokens._cache_size() == n_take
    pool.release(pages)
    pool.release(again)
