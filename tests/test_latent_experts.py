"""Latent attention (MLA) and an expert layer held as one chip's share, on
the paged serving path, at a tiny DeepSeek-V3-shaped configuration:

* the absorbed paged decode equals the expanded attention it replaces;
* chunked prefill, then paged decode through latent pages, agrees with a
  plain full forward;
* a latent block (``c_kv`` and ``k_pe``, two widths, no head axis) moves
  byte for byte through ``HostKVPool``, ``stage_run``, ``DevicePagePool``,
  ``SSDBlockStore`` and an MKW1 frame;
* served tokens do not depend on host-pool hits;
* the held shares of a layer add up to the uncut layer;
* the expert counters match a count made on the host.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig
from repro.core.trace import BLOCK_TOKENS
from repro.models.layers import (expert_share_block, mla_attention_block,
                                 paged_mla_block)
from repro.models.transformer import (decode_step, decode_step_paged,
                                      init_caches, init_params, prefill)
from repro.serving.engine import (DecodeWorker, HostKVPool, PrefillWorker,
                                  stage_run)
from repro.serving.loop import ServingLoop
from repro.serving.paged_cache import DevicePagePool
from repro.serving.request import ServingRequest

# 16 experts, 4 of them held here ([4, 8)), top-4 by sigmoid + bias
CFG = ModelConfig(
    name="tiny-deepseek-v3", kind="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=16, d_ff=96, vocab_size=300, rope_theta=5e4,
    norm_eps=1e-5, remat=False,
    mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=16, top_k=4, d_ff_expert=24, routed_scale=2.446,
                  d_ff_shared=48, first_dense=1, expert_first=4, n_held=4))
PAGE = 16


@pytest.fixture(scope="module")
def params():
    p = init_params(CFG, jax.random.PRNGKey(3))
    # a drawn selection bias, so that picks are not the scores' alone
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(4),
                                   p["moe"]["bias"].shape)
    return dict(p, moe=dict(p["moe"], bias=bias))


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_latent_widths_are_read_from_the_config():
    assert CFG.kv_widths == ((32,), (8,))
    assert CFG.kv_token_bytes == 2 * 3 * (32 + 8)
    pp = DevicePagePool(CFG, n_pages=5, page_tokens=PAGE)
    assert pp.k_pages.shape == (3, 5, PAGE, 32)
    assert pp.v_pages.shape == (3, 5, PAGE, 8)
    assert pp.page_bytes == 3 * PAGE * (32 + 8) * 2


def test_absorbed_paged_decode_equals_expanded_attention(params):
    """One layer at three slots of different depths: the absorbed form
    over pages against the expanded form over a dense arena of the same
    latent history. In float32 the two differ only by how the products
    are associated ((q·W_UK)·c against q·(W_UK·c)), so 1e-4 of the
    output's scale holds them."""
    p = f32(params["attn"])
    p = jax.tree.map(lambda a: a[0], p)
    rng = np.random.default_rng(0)
    B, n_pages, L = 3, 4, CFG.n_layers
    lens = np.array([5, 17, 40], np.int32)
    c_hist = rng.standard_normal((B, n_pages * PAGE, 32)).astype(np.float32)
    pe_hist = rng.standard_normal((B, n_pages * PAGE, 8)).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((B, 1, CFG.d_model)), jnp.float32)
    # the paged store: slot b owns pages 1 + b*n .. ; page 0 is null
    P = 1 + B * n_pages
    c_pages = np.zeros((L, P, PAGE, 32), np.float32)
    pe_pages = np.zeros((L, P, PAGE, 8), np.float32)
    table = np.zeros((B, n_pages), np.int32)
    for b in range(B):
        for j in range(n_pages):
            pid = 1 + b * n_pages + j
            table[b, j] = pid
            c_pages[1, pid] = c_hist[b, j * PAGE:(j + 1) * PAGE]
            pe_pages[1, pid] = pe_hist[b, j * PAGE:(j + 1) * PAGE]
    y_paged, (cp, pep) = paged_mla_block(
        x, p, CFG, c_pages=jnp.asarray(c_pages), pe_pages=jnp.asarray(pe_pages),
        layer=1, block_table=jnp.asarray(table), seq_lens=jnp.asarray(lens))
    arena = (jnp.zeros((L, B, n_pages * PAGE, 32)).at[1].set(c_hist),
             jnp.zeros((L, B, n_pages * PAGE, 8)).at[1].set(pe_hist))
    y_dense, (c_all, pe_all) = mla_attention_block(
        x, p, CFG, positions=jnp.asarray(lens)[:, None], cache=arena,
        layer=1)
    scale = float(jnp.abs(y_dense).max())
    np.testing.assert_allclose(np.asarray(y_paged), np.asarray(y_dense),
                               atol=1e-4 * scale, rtol=0)
    # both wrote the new token's latent pair at its position
    for b in range(B):
        pid, off = table[b, lens[b] // PAGE], lens[b] % PAGE
        np.testing.assert_array_equal(cp[1, pid, off], c_all[1, b, lens[b]])
        np.testing.assert_array_equal(pep[1, pid, off], pe_all[1, b, lens[b]])


def test_prefill_then_paged_decode_agrees_with_full_forward(params):
    """Prompts of 37, 9 and 23 tokens prefilled in chunks of 8, staged into
    latent pages, then 6 greedy paged decode steps of the three slots
    together: every step's logits against one full forward pass over the
    whole sequence (``decode_step`` of all of it on an empty cache, every
    position's logits). Float32 weights and a bf16 latent cache on both
    sides; what differs is the absorbed against the expanded product
    order, and a latent element that chunks and steps computed a few ulps
    of float32 away from the full pass may round to the neighbouring bf16
    value (2^-8 of it): 1e-2 of the logits' scale (seen: 2e-3)."""
    p = f32(params)
    ext = jax.jit(lambda c, t: decode_step(p, t, c, CFG))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size, n) for n in (37, 9, 23)]
    pp = DevicePagePool(CFG, n_pages=1 + 3 * 4, page_tokens=PAGE)
    table = np.zeros((3, 4), np.int32)
    lens = np.array([len(t) for t in prompts], np.int32)
    seqs, served = [], []
    for b, prompt in enumerate(prompts):
        caches = init_caches(CFG, 1, len(prompt))
        for lo in range(0, len(prompt), 8):
            logits, caches = ext(caches, jnp.asarray(prompt[None, lo:lo + 8],
                                                     jnp.int32))
        table[b] = pp.alloc(4)
        pp.write_run(table[b, :pp.pages_for(len(prompt))].tolist(),
                     caches.kv.k[:, 0], caches.kv.v[:, 0])
        seqs.append(list(prompt) + [int(jnp.argmax(logits[0, -1]))])
        served.append([])
    step = jax.jit(lambda *a: decode_step_paged(p, *a, CFG))
    kp, vp = pp.k_pages, pp.v_pages
    for _ in range(6):
        toks = jnp.asarray([[s[-1]] for s in seqs], jnp.int32)
        # .copy(): jax-CPU may alias a numpy buffer that the next line
        # bumps while the step still reads it
        logits, kp, vp, _ = step(toks, kp, vp, jnp.asarray(table),
                                 jnp.asarray(lens.copy()))
        lens += 1
        for b in range(3):
            served[b].append(np.asarray(logits[b, 0]))
            seqs[b].append(int(jnp.argmax(logits[b, 0])))
    for b, prompt in enumerate(prompts):
        seq = jnp.asarray(seqs[b][:-1], jnp.int32)[None]
        full, _ = decode_step(p, seq, init_caches(CFG, 1, seq.shape[1]), CFG)
        ref = np.asarray(full[0, len(prompt):])
        np.testing.assert_allclose(np.stack(served[b]), ref,
                                   atol=1e-2 * float(np.abs(ref).max()),
                                   rtol=0)


def _block(rng, n_tokens=BLOCK_TOKENS):
    import ml_dtypes
    k = rng.standard_normal((CFG.n_layers, n_tokens, 32))
    v = rng.standard_normal((CFG.n_layers, n_tokens, 8))
    return k.astype(ml_dtypes.bfloat16), v.astype(ml_dtypes.bfloat16)


@pytest.mark.parametrize("source", ["host", "device"])
def test_latent_block_round_trips_pool_stage_and_pages(source):
    """Two latent blocks and a tail into ``HostKVPool`` and back, then
    through ``stage_run`` into pages from the host arrays, or from a B=1
    dense arena on the device as a finished prefill stages them: read
    back byte for byte."""
    rng = np.random.default_rng(2)
    S = 2 * BLOCK_TOKENS + 40
    k, v = _block(rng, S)
    pool = HostKVPool()
    pool.put([11, 12], k[:, :2 * BLOCK_TOKENS], v[:, :2 * BLOCK_TOKENS])
    gk, gv = pool.get([11, 12])
    np.testing.assert_array_equal(gk, k[:, :2 * BLOCK_TOKENS])
    np.testing.assert_array_equal(gv, v[:, :2 * BLOCK_TOKENS])
    pp = DevicePagePool(CFG, n_pages=1 + 2 * 8 * 4, page_tokens=64)
    if source == "host":
        src = (k, v)
    else:
        src = (jnp.asarray(k)[:, None], jnp.asarray(v)[:, None])
    pages = stage_run(pp, [11, 12], *src, S)
    rk, rv = pp.read_seq(pages, S)
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    # the registered blocks are adopted, not written again, by a second run
    written = pp.pages_written
    again = stage_run(pp, [11, 12], *src, 2 * BLOCK_TOKENS)
    assert again == pages[:2 * 8] and pp.pages_written == written
    pp.release(pages)
    pp.release(again)
    pp.check_leaks()


def test_latent_block_survives_ssd_store_and_frame(tmp_path):
    """A latent block through the file-backed store (flushed, reopened,
    read whole and by layer) and an MKW1 layer frame."""
    from repro.serving.ssd_store import SSDBlockStore
    from repro.serving.transport import (MSG_LAYER, FrameReader,
                                         encode_frame, pack_layer,
                                         unpack_layer)
    rng = np.random.default_rng(3)
    k, v = _block(rng)
    store = SSDBlockStore(str(tmp_path), writeback_batch=1)
    store.put(7, k, v)
    assert store.block_bytes == k.nbytes + v.nbytes
    store.close()
    store = SSDBlockStore(str(tmp_path), writeback_batch=1)
    rk, rv = store.read_block(7)
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    lk, lv = store.read_layer(7, 2)
    np.testing.assert_array_equal(lk, k[2])
    np.testing.assert_array_equal(lv, v[2])
    store.close()
    frame = encode_frame(MSG_LAYER, pack_layer(7, 2, k[2], v[2]))
    [(kind, payload)] = FrameReader().feed(frame)
    assert kind == MSG_LAYER
    meta, fk, fv = unpack_layer(payload)
    assert (meta["key"], meta["layer"]) == (7, 2)
    np.testing.assert_array_equal(fk, k[2])
    np.testing.assert_array_equal(fv, v[2])


def _req(rid, toks, max_new):
    return ServingRequest(req_id=rid, tokens=toks, max_new=max_new)


def test_served_tokens_with_host_pool_hits_equal_a_run_without(params):
    """Requests sharing a 512-token system block through ``ServingLoop``,
    one engine and one host pool (later requests hit the stored latent
    blocks), against each request alone on a fresh engine (no hits)."""
    rng = np.random.default_rng(4)
    shared = rng.integers(0, CFG.vocab_size, BLOCK_TOKENS)
    reqs = {i: np.concatenate([shared, rng.integers(0, CFG.vocab_size,
                                                    30 + 70 * i)])
            for i in range(3)}

    def engine():
        pool = HostKVPool()
        dw = DecodeWorker(params, CFG, max_batch=4, max_len=1024)
        pw = PrefillWorker(params, CFG, pool, prefill_chunk=128,
                           page_pool=dw.page_pool)
        return pw, dw

    pw, dw = engine()
    loop = ServingLoop([pw], dw, chunks_per_iter=1)
    for batch in ([0], [1, 2]):     # 0 stores the block the others hit
        for i in batch:
            assert loop.submit(_req(i, reqs[i], 6))
        while not loop.idle:
            loop.iterate()
    assert pw.stats()["reused_blocks"] == 2
    for i, t in reqs.items():
        pw1, dw1 = engine()
        res = pw1(t)
        assert res.reused_blocks == 0
        dw1.join(_req(i, t, 6), res)
        seq = [res.first_token]
        while dw1.n_active:
            seq += [tok for _, tok, _ in dw1.step()]
        assert loop.outputs[i].tokens == seq, f"request {i} diverged"
    dw.page_pool.check_leaks()


def test_held_shares_add_up_to_the_uncut_layer(params):
    """The four shares of a 16-expert layer (4 experts each), with the
    shared expert counted once, against the layer holding all 16."""
    p = f32(jax.tree.map(lambda a: a[0], params["moe"]))
    full = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, n_held=0, expert_first=0))
    E = 16
    rng = np.random.default_rng(5)
    ws = {n: jnp.asarray(rng.standard_normal((E,) + p[n].shape[1:]) * 0.2,
                         jnp.float32) for n in ("w1", "w2", "w3")}
    x = jnp.asarray(rng.standard_normal((2, 5, CFG.d_model)), jnp.float32)
    y_full, picked_full = expert_share_block(x, dict(p, **ws), full)
    # a share that holds no routed expert gives the shared expert alone
    none = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, n_held=1, expert_first=E))
    y_shared, _ = expert_share_block(
        x, dict(p, **{n: w[:1] for n, w in ws.items()}), none)
    total = -3 * y_shared
    picks = []
    for first in range(0, E, 4):
        cut = dataclasses.replace(CFG, moe=dataclasses.replace(
            CFG.moe, expert_first=first, n_held=4))
        y, picked = expert_share_block(
            x, dict(p, **{n: w[first:first + 4] for n, w in ws.items()}), cut)
        total = total + y
        picks.append(picked)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               atol=1e-5 * float(jnp.abs(y_full).max()))
    np.testing.assert_array_equal(np.concatenate(picks, axis=1), picked_full)
    assert int(picked_full.sum()) == 10 * CFG.moe.top_k


def _forced(params, experts):
    """Params whose selection bias makes every token pick ``experts``."""
    bias = jnp.zeros_like(params["moe"]["bias"]).at[:, experts].set(100.0)
    return dict(params, moe=dict(params["moe"], bias=bias))


@pytest.mark.parametrize("experts,held_picks", [([4, 5, 0, 1], 2),
                                                ([0, 1, 2, 9], 0),
                                                ([4, 5, 6, 7], 4)])
def test_expert_counters_match_a_host_count(params, experts, held_picks):
    """With the bias forcing every token onto ``experts`` (``held_picks``
    of them held here), ``moe_touched``/``moe_held`` of the decode worker
    and ``moe_pairs_held``/``moe_pairs`` of the prefill worker match what
    the host counts from the steps and tokens run."""
    p = _forced(params, experts)
    pool = HostKVPool()
    dw = DecodeWorker(p, CFG, max_batch=4, max_len=256)
    pw = PrefillWorker(p, CFG, pool, prefill_chunk=16, page_pool=dw.page_pool)
    rng = np.random.default_rng(6)
    n_moe = CFG.n_layers - CFG.moe.first_dense
    tokens = 0
    for i, n in enumerate((20, 33)):
        t = rng.integers(0, CFG.vocab_size, n)
        dw.join(_req(i, t, 5), pw(t))
        tokens += n
    steps = 0
    while dw.n_active:
        dw.step()
        steps += 1
    ds, ps = dw.stats(), pw.stats()
    assert ds["moe_held"] == steps * n_moe * 4
    assert ds["moe_touched"] == steps * n_moe * held_picks
    assert ps["moe_pairs"] == tokens * n_moe * CFG.moe.top_k
    assert ps["moe_pairs_held"] == tokens * n_moe * held_picks


def test_dense_workers_report_no_expert_counters():
    from repro.configs.base import get_config
    cfg = get_config("smollm-360m").reduced()
    p = init_params(cfg, jax.random.PRNGKey(0))
    dw = DecodeWorker(p, cfg, max_batch=2, max_len=128)
    pw = PrefillWorker(p, cfg, HostKVPool(), prefill_chunk=64,
                       page_pool=dw.page_pool)
    assert "moe_touched" not in dw.stats()
    assert "moe_pairs_held" not in pw.stats()


def test_sharded_and_kernel_paths_refuse_latent_attention():
    from repro.models.transformer import paged_shard_reason
    assert "latent" in paged_shard_reason(CFG, 2)
    p = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="absorbed"):
        jax.eval_shape(lambda: decode_step_paged(
            p, jnp.zeros((1, 1), jnp.int32), jnp.zeros((3, 2, PAGE, 32)),
            jnp.zeros((3, 2, PAGE, 8)), jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), CFG, use_pallas=True))
